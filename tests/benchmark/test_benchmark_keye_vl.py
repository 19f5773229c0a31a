"""The benchmark's additions for ``keye-vl-2.0-30b-a3b-ep8``: the issue's
parameter table from ``families/keye_vl.py``, the counts of operations and
bytes (attention and the objective over the SELECTED pairs, the indexer over
the causal ones), the configuration file against the catalog's published
``config.json``, the manifest's entries (by containment: where they stand in
their lists, and what later PRs append behind them, is not this file's to
hold), the three new readers, the scope groups against the program's own
scopes, and the cell's rehearsal on the CPU."""

import re

import jax
import pytest

from bench_helpers import CONTRACT_KEYS, rehearse  # first: sets sys.path
from benchmark import manifest, scope_reduce, weights
from benchmark.families import keye_vl as family

CELL = "keyevl-train-1chip"
CONFIG = "keye-vl-2.0-30b-a3b-ep8"
# The published config.json (the catalog's row beside the model-configs guide).
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
NEW_READERS = ("sparse_index_ms.train", "sparse_index_fwd_roofline",
               "sparse_index_loss_ms.train", "sparse_index_select_roofline",
               "sparse_index_kl_roofline")
SHARED_READERS = ("attn_fwd_roofline", "attn_bwd_roofline",
                  "attn_bwd_ms.train", "head_loss_ms.train",
                  "optimizer_ms.train", "scope_unnamed_share.train",
                  "moe_route_ms.train", "moe_route_kernel_ms.train",
                  "moe_experts_ms.train", "moe_experts_roofline")
T = 16384
SELECTED = 2048 * 2049 // 2 + (T - 2048) * 2048      # sum_t min(t + 1, 2048)
CAUSAL = T * (T + 1) // 2


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


_count = weights.count


def test_parameter_counts_of_the_sizing_table(cell):
    spec = family.param_spec(cell.config)
    layer = spec["layer_0"]
    attn = dict(layer["self_attn"])
    indexer = attn.pop("indexer")
    assert _count(attn) == 18_874_624        # q, k, v, o and the two norms
    assert _count(indexer) == 2_261_120 == (
        2048 * 1024 + 2048 * 64 + 2048 * 16 + 128)
    assert indexer["wk"]["kernel"].shape == (2048, 64)       # ONE key head
    assert indexer["k_norm"]["bias"] == weights.Leaf((64,), "zeros")
    assert _count(layer["mlp"]["router"]) == 262_144
    assert _count(layer["mlp"]["experts"]) == 75_497_472 == 16 * 4_718_592
    assert layer["mlp"]["experts"]["gate"].shape == (16, 2048, 768)
    assert _count(layer["input_layernorm"]) + _count(
        layer["post_attention_layernorm"]) == 4_096
    assert _count(layer) == 96_899_456
    assert all(_count(spec[f"layer_{i}"]) == _count(layer) for i in range(6))
    assert "layer_6" not in spec and "shared_expert" not in layer
    assert (_count(spec["embed_tokens"]) + _count(spec["lm_head"])
            + _count(spec["norm"])) == 77_793_280 == 2 * 18992 * 2048 + 2048
    total = _count(spec)
    assert total == 659_190_016 == cell.config["parameters"]
    # f32 parameters, gradients and AdamW's two moments: 16 bytes each
    assert round(total * 16 / 1e9, 2) == 10.55


def test_operation_counts(cell):
    cfg, traffic = cell.config, cell.traffic
    assert family.selected_pairs(cfg, traffic) == SELECTED == 31_458_304
    assert family.causal_pairs(traffic) == CAUSAL == 134_225_920
    assert round(SELECTED / CAUSAL, 3) == 0.234
    assert family.expected_held_per_token(cfg) == 1.0
    per_token = family.matmul_params_per_token(cfg)
    assert per_token == 6 * (18_874_368 + 2_260_992 + 262_144
                             + 3 * 2048 * 768) + 2048 * 18992
    assert round(per_token / 1e6, 1) == 195.6
    # QK^T and PV over the selected pairs and the 32 heads of 128
    ops, nbytes = family.attn_fwd_cost(cfg, traffic, 1)
    assert ops == 6 * SELECTED * 4 * 32 * 128
    assert round(ops / 6 / 1e12, 3) == 0.515        # a layer and forward pass
    assert nbytes == 6 * 4 * T * 4096 * 2
    assert family.attn_fwd_calls(cfg) == 6
    # the indexer: three projections and 2 x 16 x 64 a causal pair
    index_ops, index_bytes = family.sparse_index_fwd_cost(cfg, traffic, 1)
    assert index_ops == 6 * (2 * T * 2_260_992 + CAUSAL * 2 * 1024)
    assert round(index_ops / 6 / 1e12, 3) == 0.349
    peak = manifest.peak_for("TPU v5 lite")
    assert index_ops / peak["bf16_flops"] > 5 * (
        index_bytes / peak["hbm_bytes_per_s"])      # compute-bound
    select_ops, _ = family.sparse_index_select_cost(cfg, traffic, 1)
    assert select_ops == 6 * CAUSAL * 2 * 1024      # without the projections
    assert family.sparse_index_calls(cfg) == 6
    # the objective with its gradient, over the selected pairs
    kl_ops, kl_bytes = family.sparse_index_kl_cost(cfg, traffic, 1)
    assert kl_ops == 6 * SELECTED * (2 * 4096 + 6 * 1024)
    assert kl_ops / peak["bf16_flops"] > 5 * (
        kl_bytes / peak["hbm_bytes_per_s"])
    total = family.train_ops_per_step(cfg, traffic, 1)
    scores, objective = select_ops, kl_ops
    assert total == 6 * per_token * T + 3 * ops + scores + objective
    assert round(total / 1e12, 1) == 32.9
    # what the selection brings (attention, scores, objective) is two fifths
    # of the LEAST work; in masked form (every causal pair) it is far more
    assert round((3 * ops + scores + objective) / total, 2) == 0.41
    # the held experts: 16384 expected pairs a layer (a held expert sees 1024
    # tokens, an eighth of its EP8 load)
    ops, nbytes = family.moe_experts_cost(cfg, traffic, 1)
    assert ops == 6 * T * 6 * 2048 * 768
    assert T * cfg["num_experts_per_tok"] // 128 == 1024
    assert nbytes == 6 * (16 * 3 * 2048 * 768 * 2 + T * 2 * 2048 * 2)


def test_configuration_file_states_every_published_size(cell):
    cfg = cell.config
    entry = next(c for c in cell.manifest["configs"] if c["name"] == CONFIG)
    assert cell.manifest["configs"].count(entry) == 1
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_local_experts"], cfg["vocab_size"]) == (6, 16, 16, 18992)
    assert cfg["num_hidden_layers"] >= 4              # the guide's floor
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["num_experts_routed"] == 128
    assert cfg["first_expert_held"] == 0
    # no width is cut
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "sa_config", "rope_scaling"):
        assert key not in cfg["reduced"] and cfg[key] == PUBLISHED[key]
    assert "eight chips share each layer" in cfg["deployment"]
    assert "8 stages of 6" in cfg["deployment"]
    for word in ("per-head RMSNorm", "LayerNorm", "coefficient 1",
                 "initializer_range", "AdamW 1e-5", "-0.5"):
        assert any(word in a for a in cfg["assumed"]), word
    for word in ("Hadamard", "vision tower", "no pooling", "shared expert",
                 "top_k"):
        assert any(word in d for d in cfg["departures"]), word
    assert "HALVES" in cfg["layout"]
    assert cfg["source"] == entry["source"]
    assert cfg["train"]["stated_precision"] == "bfloat16"
    assert cfg["train"]["control_precision"] == "int8"
    assert cfg["train"]["remat"] is True
    assert cfg["train"]["optimizer"]["learning_rate"] == 1e-5
    model = family.model_config(cfg)
    assert (model.n_layers, model.experts_held, model.n_experts, model.top_k,
            model.index_heads, model.index_head_dim, model.index_top_k,
            model.mrope_section) == (6, 16, 128, 8, 16, 64, 2048, (16, 24, 24))
    with pytest.raises(ValueError, match="one indexer key head"):
        family.model_config({**cfg, "sa_config": {
            **cfg["sa_config"], "indexer_num_kv_heads": 2}})
    # the rehearsal's selection binds: top 16 of 128 positions
    small = manifest.Cell(cell.manifest, CELL, rehearse=True)
    assert small.config["sa_config"]["topk"] == 16
    assert small.config["sa_config"]["indexer_num_kv_heads"] == 1
    assert small.traffic["seq_len"] == 128


def test_manifest_entries_of_the_cell(cell):
    assert cell.manifest["workloads"].count(cell.entry) == 1
    assert cell.entry == {
        "name": CELL, "config": CONFIG, "traffic": "lm-train-t16384",
        "chips": 1, "why": cell.entry["why"]}
    assert len(cell.entry["why"]) <= 200
    assert "1024 tokens" in cell.entry["why"]      # how near the EP8 load
    # the cells that were there are there still
    assert {"gpt2m-train-1chip", "gpt2m-train-dp4", "qwen3next-train-1chip",
            "lfm2moe-train-1chip", "xing4-train-1chip"} <= {
        w["name"] for w in cell.manifest["workloads"]}
    assert cell.chips == 1 and cell.options["mesh"] == {"data": 1}
    assert cell.options["step_options"] == {}
    assert (cell.traffic["seq_len"], cell.traffic["per_chip_batch"],
            cell.traffic["pool_batches"], cell.traffic["fetch_every"],
            cell.traffic["check_steps"], cell.traffic["warm_steps"]) == (
        16384, 1, 16, 10, 3, 2)
    assert {"train_samples_per_s_per_chip", "setup_s"} <= {
        m["name"] for m in cell.end_to_end()}
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_READERS) | set(SHARED_READERS) | {
        "mfu.train", "step_device_ms.train", "dispatch_ms.train",
        "device_idle_share.train", "peak_hbm_gb.train", "compiles_in_window",
        "kernel_fallbacks.train"} <= names
    for name in names:
        assert hasattr(manifest.load_reader(name), "compute")
    declared = {m["name"]: m for m in cell.manifest["per_layer"]}
    new = [declared[name] for name in NEW_READERS]
    assert all(m["workloads"] == [CELL] and m["source"] == "device_trace"
               and m["moves"] == "train_samples_per_s_per_chip"
               and m["layer"] == "Sparse selection" for m in new)
    assert [(m["unit"], m["better"]) for m in new] == [
        ("ms", "lower"), ("%", "higher"), ("ms", "lower"), ("%", "higher"),
        ("%", "higher")]
    for name in SHARED_READERS:
        assert CELL in declared[name]["workloads"], name
    limits = cell.options["limits"]
    assert set(limits) == {"loss_rel", "first_grad_norm", "update_norm",
                           "nonfinite_losses"}
    assert "calibrate" in cell.options["limits_set_from"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_where_there_is_nothing(cell, name):
    """No device trace, or a cell of another family (no
    ``sparse_index_fwd_cost``, no such group; a parent whose step has no such
    scope): ``None``, no raise."""
    class Run:
        trace = False
        counters = {"per_chip_batch": 1}

    run = Run()
    run.cell = cell
    assert manifest.load_reader(name).compute(run) is None
    other = Run()
    other.cell = manifest.Cell(manifest.load_manifest(), "lfm2moe-train-1chip")
    other._scope_reduction = {"groups_ms": {"gqa_attn": 3.0}}
    assert manifest.load_reader(name).compute(other) is None


def test_readers_read_their_groups(cell):
    class Device:
        device_kind = "TPU v5 lite"

    class Run:
        trace = True
        counters = {"per_chip_batch": 1}
        devices = [Device()]

    run = Run()
    run.cell = cell
    roofline = manifest.load_reader("sparse_index_fwd_roofline")
    least, which = roofline.bound(run)
    assert which == "compute" and round(least * 1e3, 2) == 10.63
    run._scope_reduction = {"groups_ms": {
        "sparse_index_fwd": 100.0, "sparse_index_bwd": 150.0,
        "sparse_index_loss": 400.0}}
    assert roofline.compute(run) == pytest.approx(10.63, abs=0.005)
    assert manifest.load_reader("sparse_index_ms.train").compute(run) == 250.0
    assert manifest.load_reader("sparse_index_loss_ms.train").compute(
        run) == 400.0
    run._scope_reduction = {"groups_ms": {"sparse_index_fwd": 0.0}}
    assert roofline.compute(run) is None
    assert manifest.load_reader("sparse_index_ms.train").compute(run) is None
    assert manifest.load_reader("sparse_index_loss_ms.train").compute(
        run) is None


def _launch(ops):
    """A trace of chip 0 with four launches of ``jit_step`` holding ``ops``
    (``(name, duration ns)``) one after another."""
    modules, events, t = [], [], 0
    for _ in range(4):
        start = t
        for name, dur in ops:
            events.append([name, t, dur])
            t += dur
        modules.append(["jit_step(1)", start, t - start])
        t += 10
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": events}]}]}


def test_kernel_readers_go_by_the_kernels_names(cell, capsys):
    """``sparse_index_select_roofline`` holds one pass's bound against one
    pass's share of the events ``sparse_index_select.<n>`` (two passes a step
    with recomputation on), ``sparse_index_kl_roofline`` the objective's
    bound against ALL of ``sparse_index_kl.<n>`` (the first pass's value-only
    call is overhead); a trace without the events (a program that selects by
    plain XLA, the parent) gives nothing."""
    class Device:
        device_kind = "TPU v5 lite"

    class Run:
        trace = True
        counters = {"per_chip_batch": 1, "launch_pattern": r"^jit_step\("}
        devices = [Device()]

        def launch_match(self):
            return lambda name: name.startswith("jit_step(")

    run = Run()
    run.cell = cell
    select = manifest.load_reader("sparse_index_select_roofline")
    kl = manifest.load_reader("sparse_index_kl_roofline")
    least, which = select.bound(run)
    assert which == "compute" and round(least * 1e3, 2) == 8.37
    least_kl, which = kl.bound(run)
    assert which == "compute" and round(least_kl * 1e3, 2) == 13.74
    ms = 1_000_000
    run.device_trace = _launch(
        [(f"sparse_index_select.{n}", 10 * ms) for n in range(12)]
        + [(f"sparse_index_kl.{n}", 20 * ms) for n in range(12)]
        + [("fusion.1", 5 * ms), ("attention.3", 7 * ms)])
    # 12 calls of 10 ms are two passes of 60 ms
    assert select.compute(run) == pytest.approx(100 * 8.37 / 60, rel=1e-3)
    assert kl.compute(run) == pytest.approx(100 * 13.74 / 240, rel=1e-3)
    out = capsys.readouterr().out
    assert '"passes": 2.0' in out and "sparse_index_kl_kernel:" in out
    run.device_trace = _launch([("fusion.1", 5 * ms), ("attention.3", 7 * ms)])
    assert select.compute(run) is None and kl.compute(run) is None


_OP_NAME = re.compile(r'op_name="([^"]+)"')


def test_groups_name_every_scope_and_leave_little_unnamed():
    """The groups file against the program: every scope the model enters has
    a group of its own, the objective's group comes before the indexer's
    (whose name it contains) and the kernels' groups before the mixer's that
    holds them, and of the compiled rehearsal step's operations that carry a
    scope path only a few fall to `unnamed`."""
    import horovod_tpu.jax as hvd
    import jax.numpy as jnp
    from horovod_tpu import trace

    groups = scope_reduce.Groups("keye_vl")
    doc = manifest.load_json(scope_reduce.groups_file("keye_vl"))
    other = manifest.load_json(scope_reduce.groups_file("lfm2_moe"))
    assert tuple(doc["model_scopes"]) == trace.KEYE_SCOPES
    assert doc["program_scope"] == other["program_scope"]
    assert doc["scopes"] == other["scopes"]
    kept = {r["group"]: (r["path"], r.get("op")) for r in other["rules"]}
    for r in doc["rules"]:
        if r["group"] in kept and r["group"] not in ("embed", "head_loss"):
            assert (r["path"], r.get("op")) == kept[r["group"]], r["group"]
    order = [r["group"] for r in doc["rules"]]
    assert order.index("sparse_index_loss") < order.index("sparse_index_fwd")
    assert order.index("attn_fwd") < order.index("gqa_attn")
    for scope in trace.KEYE_SCOPES + (trace.SCOPE_FLASH_BWD,):
        assert any(p.search(f"jit(step)/x/{scope}/dot_general")
                   for _, p, _ in groups.rules), scope
    top = "jit(step)/hvd_loss_grad/"
    again = top + "transpose(jvp(KeyeVLLM))/"
    for opcode, path, group in [
        ("fusion", top + "jvp(KeyeVLLM)/layer_0/self_attn/sparse_index/"
         "indexer/wq/dot_general", "sparse_index_fwd"),
        ("fusion", top + "jvp(KeyeVLLM)/layer_0/self_attn/sparse_index/"
         "while/body/reduce_sum", "sparse_index_fwd"),
        ("fusion", again + "layer_0/self_attn/sparse_index/while/body/"
         "reduce_sum", "sparse_index_bwd"),
        ("fusion", top + "jvp(KeyeVLLM)/layer_0/self_attn/sparse_index_loss/"
         "while/body/exp", "sparse_index_loss"),
        ("fusion", again + "layer_0/self_attn/sparse_index_loss/mul",
         "sparse_index_loss"),
        ("fusion", top + "jvp(KeyeVLLM)/layer_1/self_attn/gqa_attn/q_proj/"
         "dot_general", "gqa_attn"),
        ("custom-call", top + "jvp(KeyeVLLM)/layer_1/self_attn/gqa_attn/"
         "attention/pallas_call", "attn_fwd"),
        ("custom-call", again + "layer_1/self_attn/gqa_attn/attention/"
         "flash_bwd/pallas_call", "attn_bwd"),
        ("fusion", top + "jvp(KeyeVLLM)/layer_2/mlp/moe_route/sort",
         "moe_route"),
        ("fusion", top + "jvp(KeyeVLLM)/layer_2/mlp/moe_experts/mul",
         "moe_experts_fwd"),
        ("fusion", top + "jvp(KeyeVLLM)/layer_2/input_layernorm/mul",
         "blocks_fwd"),
        ("fusion", top + "jvp(KeyeVLLM)/lm_head/dot_general", "head_loss"),
        ("fusion", top + "jvp(KeyeVLLM)/embed_tokens/take", "embed"),
    ]:
        assert scope_reduce.group_of(groups.rules, opcode, path) == group, path
    # moe_experts_roofline finds the backward's beginning by the head's
    # transposed product
    reader = manifest.load_reader("moe_experts_roofline")
    assert reader.BACKWARD.search(again + "lm_head/dot_general")

    cell = manifest.Cell(manifest.load_manifest(), CELL, rehearse=True)
    mesh = hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])
    step, tx = cell.family.build_train(cell.config, cell.traffic, {}, mesh)
    params = weights.make_params(cell.family.param_spec(cell.config), 3)
    tokens = jnp.zeros((2, cell.traffic["seq_len"]), jnp.int32)
    text = step.lower(params, tx.init(params),
                      (tokens, tokens)).compile().as_text()
    seen = {}
    for line in text.splitlines():
        path = _OP_NAME.search(line)
        if not path or " = " not in line or "/" not in path.group(1):
            continue
        opcode = scope_reduce._OPCODE.search(line.partition(" = ")[2])
        group = scope_reduce.group_of(groups.rules,
                                      opcode.group(1) if opcode else "",
                                      path.group(1))
        seen[group] = seen.get(group, 0) + 1
    total = sum(seen.values())
    assert seen.get(scope_reduce.UNNAMED, 0) < 0.02 * total, seen
    for group in ("sparse_index_fwd", "sparse_index_bwd", "sparse_index_loss",
                  "gqa_attn", "attn_bwd", "moe_experts_fwd", "moe_experts_bwd",
                  "moe_route", "head_loss", "embed", "blocks_fwd",
                  "blocks_bwd", "optimizer"):
        assert seen.get(group, 0) > 0, (group, seen)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_comes_out_correct(trace):
    """The cell end to end on the CPU at its rehearsal sizes, the command as
    the driver gives it, untraced and traced (the committed limits are the
    chip's, at full size; the rehearsal's are in the cell's file too)."""
    line, out = rehearse(CELL, seed=2147483659 + trace, seconds=0.5,
                         trace=trace, timeout=600)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert set(line) == CONTRACT_KEYS | {"breakdown"}
        # no TPU plane in a CPU trace: the device readers, the new ones
        # among them, find nothing and are left out
        assert "dispatch_ms.train" in line["metrics"]
        assert not any(name.startswith(("sparse_index", "moe_", "attn_"))
                       for name in line["metrics"])
    else:
        assert set(line) == CONTRACT_KEYS
        assert set(line["metrics"]) >= {"train_samples_per_s_per_chip",
                                        "setup_s"}
    assert '"number": "first_grad_norm"' in out
