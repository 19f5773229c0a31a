"""The benchmark's additions for ``lfm2-8b-a1b-ep4``: the counts of the
issue's sizing table from ``families/lfm2_moe.py``, the configuration file
against the catalog's published ``config.json``, the manifest's entries, the
new readers, and the scope groups against the program's own scopes."""

import re

import jax
import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import manifest, scope_reduce, weights
from benchmark.families import lfm2_moe as family

CELL = "lfm2moe-train-1chip"
CONFIG = "lfm2-8b-a1b-ep4"
PERIOD = ["conv", "conv", "full_attention", "conv", "conv", "conv",
          "full_attention", "conv", "conv", "conv", "full_attention", "conv",
          "conv", "conv", "full_attention", "conv", "conv", "conv",
          "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
# The published config.json (the catalog's row beside the model-configs guide).
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": PERIOD,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
NEW_READERS = ("short_conv_ms.train", "short_conv_fwd_roofline")
SHARED_READERS = ("attn_fwd_roofline", "attn_bwd_roofline",
                  "attn_bwd_ms.train", "head_loss_ms.train",
                  "optimizer_ms.train", "scope_unnamed_share.train",
                  "moe_route_ms.train", "moe_experts_ms.train",
                  "moe_experts_roofline")


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


_count = weights.count


def test_parameter_counts_of_the_sizing_table(cell):
    spec = family.param_spec(cell.config)
    mega = lambda tree: round(_count(tree) / 1e6, 2)
    dense, attn, conv = spec["layer_0"], spec["layer_1"], spec["layer_2"]
    assert mega(conv["conv"]) == 16.78
    assert mega(attn["self_attn"]) == 10.49
    assert mega(dense["feed_forward"]) == 44.04
    assert mega(conv["feed_forward"]) == 88.15
    assert mega(jax.tree.map(lambda l: l._replace(shape=l.shape[1:]),
                             conv["feed_forward"]["experts"],
                             is_leaf=weights.is_leaf)) == 11.01
    assert mega(spec["embed_tokens"]) == 33.55
    assert mega(dense) == 60.83 and mega(attn) == 98.64
    assert [mega(spec[f"layer_{i}"]) for i in (2, 3, 4)] == [104.93] * 3
    total = _count(spec)
    assert total == 507_820_288 and round(total / 1e6, 1) == 507.8
    # f32 parameters, gradients and AdamW's two moments: 16 bytes each
    assert round(total * 16 / 1e9, 1) == 8.1
    # the reference's trees (parameters, two moments, two gradients)
    assert round(total * 4 * 5 / 1e9, 1) == 10.2
    # the selection bias is a leaf of its own, drawn with its own deviation
    bias = conv["feed_forward"]["expert_bias"]
    assert bias == weights.Leaf((32,), "normal", 0.005)
    assert "lm_head" not in spec               # the head is the embedding


def test_layer_pattern_is_a_dense_layer_and_one_whole_period(cell):
    cfg = cell.config
    assert cfg["layer_types"] == PERIOD[1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["num_dense_layers"] == 1 and cfg["num_hidden_layers"] == 5
    # after the leading dense layer: one attention layer to three conv layers
    after = cfg["layer_types"][cfg["num_dense_layers"]:]
    assert (after.count("full_attention"), after.count("conv")) == (1, 3)
    m = family.dims(cfg)
    assert (m["conv_layers"], m["attn_layers"],
            m["sparse_layers"]) == (4, 1, 4)
    spec = family.param_spec(cfg)
    mixers = ["self_attn" if kind == "full_attention" else "conv"
              for kind in cfg["layer_types"]]
    assert all(k in spec[f"layer_{i}"] for i, k in enumerate(mixers))
    assert "w1" in spec["layer_0"]["feed_forward"]
    assert all("router" in spec[f"layer_{i}"]["feed_forward"]
               for i in range(1, 5))
    with pytest.raises(ValueError, match="layer_types"):
        family.dims({**cfg, "num_hidden_layers": 6})


def test_operation_counts(cell):
    cfg, traffic = cell.config, cell.traffic
    assert family.expected_held_per_token(cfg) == 1.0
    # 6 x 199.5 M multiplied weights a token x 32768 + 3.30 of attention
    per_token = family.matmul_params_per_token(cfg)
    assert round(per_token / 1e6, 1) == 199.5
    ops, nbytes = family.attn_fwd_cost(cfg, traffic, 4)
    assert round(3 * ops / 1e12, 2) == 3.30        # forward and backward
    assert nbytes == 4 * 4 * 8192 * 2048 * 2
    total = family.train_ops_per_step(cfg, traffic, 4)
    assert round(total / 1e12, 1) == 42.5
    assert total == 6 * per_token * 32768 + 3 * ops
    # the held experts: 32768 expected pairs a layer (a held expert sees
    # 4096 tokens, its EP4 load), 8.7 TFLOP of the step
    ops, nbytes = family.moe_experts_cost(cfg, traffic, 4)
    assert ops == 4 * 32768 * 6 * 2048 * 1792
    assert round(3 * ops / 1e12, 1) == 8.7
    assert 4 * 8192 * cfg["num_experts_per_tok"] // 32 == 4096
    assert nbytes == 4 * (8 * 3 * 2048 * 1792 * 2 + 32768 * 2 * 2048 * 2)
    peak = manifest.peak_for("TPU v5 lite")
    assert ops / peak["bf16_flops"] > nbytes / peak["hbm_bytes_per_s"]
    # the short convolution: 8 d bytes a token a layer, 2.1 GB a step,
    # 2.6 ms at 819 GB/s; memory-bound by far
    ops, nbytes = family.short_conv_fwd_cost(cfg, traffic, 4)
    assert nbytes == 4 * 32768 * 8 * 2048 and round(nbytes / 1e9, 1) == 2.1
    assert ops == 4 * 32768 * 2048 * 7
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    assert round(by_bytes * 1e3, 1) == 2.6
    assert by_bytes > 100 * ops / peak["bf16_flops"]


def test_configuration_file_states_every_published_size(cell):
    cfg = cell.config
    entry = next(c for c in cell.manifest["configs"] if c["name"] == CONFIG)
    assert cell.manifest["configs"].count(entry) == 1
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
        "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 8, 16384)
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 4 == cfg["num_experts_routed"] == 32
    assert cfg["first_expert_held"] == 0
    assert "four chips share each layer" in cfg["deployment"]
    assert cfg["tie_word_embeddings"] is True
    assert any("tie_word_embeddings" in a for a in cfg["assumed"])
    assert any("expert_bias_std" in a for a in cfg["assumed"])
    assert any("1 - 1e-9" in d for d in cfg["departures"])
    assert any("balancing update" in d for d in cfg["departures"])
    assert cfg["source"] == entry["source"]
    assert cfg["train"]["stated_precision"] == "bfloat16"
    assert cfg["train"]["control_precision"] == "int8"
    assert cfg["train"]["remat"] is True
    model = family.model_config(cfg)
    assert (model.n_layers, model.head_dim, model.experts_held) == (5, 64, 8)


def test_manifest_entries_of_the_cell(cell):
    # the entry is there, with these keys; where it stands in the list, and
    # what later PRs appended behind it, is not this test's to hold
    assert cell.manifest["workloads"].count(cell.entry) == 1
    assert cell.entry == {
        "name": CELL, "config": CONFIG, "traffic": "lm-train-t8192-b4",
        "chips": 1, "why": cell.entry["why"]}
    # the cells that were there are there still
    assert {"gpt2m-train-1chip", "gpt2m-train-dp4",
            "qwen3next-train-1chip"} <= {
        w["name"] for w in cell.manifest["workloads"]}
    assert cell.chips == 1 and cell.options["mesh"] == {"data": 1}
    assert cell.options["step_options"] == {}
    assert len(cell.entry["why"]) <= 200
    assert cell.entry["traffic"] == "lm-train-t8192-b4"
    assert (cell.traffic["seq_len"],
            cell.traffic["per_chip_batch"]) == (8192, 4)
    assert (cell.traffic["pool_batches"], cell.traffic["fetch_every"],
            cell.traffic["check_steps"], cell.traffic["warm_steps"],
            cell.traffic["trace_seconds"]) == (16, 10, 3, 2, 4)
    assert {"train_samples_per_s_per_chip", "setup_s"} <= {
        m["name"] for m in cell.end_to_end()}
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_READERS) | set(SHARED_READERS) | {
        "mfu.train", "step_device_ms.train", "dispatch_ms.train",
        "device_idle_share.train", "peak_hbm_gb.train"} <= names
    for name in names:
        assert hasattr(manifest.load_reader(name), "compute")
    # the two new metrics are declared for this cell, on one layer
    declared = {m["name"]: m for m in cell.manifest["per_layer"]}
    new = [declared[name] for name in NEW_READERS]
    assert all(CELL in m["workloads"] and m["layer"] == "Short convolution"
               and m["moves"] == "train_samples_per_s_per_chip" for m in new)
    assert (new[0]["unit"], new[1]["unit"]) == ("ms", "%")
    # and the shared readers list it beside the cell that was there
    for name in SHARED_READERS:
        assert {CELL, "qwen3next-train-1chip"} <= set(
            declared[name]["workloads"]), name
    limits = cell.options["limits"]
    assert set(limits) == {"loss_rel", "first_grad_norm", "update_norm",
                           "nonfinite_losses"}
    assert "calibrate" in cell.options["limits_set_from"]


@pytest.mark.parametrize("name", NEW_READERS + ("moe_route_ms.train",
                                                "moe_experts_ms.train",
                                                "moe_experts_roofline"))
def test_readers_return_nothing_without_a_trace(cell, name):
    """On a run with no device trace (and on a parent whose step has no such
    scope) the readers return None and do not raise."""
    class Run:
        trace = False
        counters = {"per_chip_batch": 4}

    run = Run()
    run.cell = cell
    assert manifest.load_reader(name).compute(run) is None


def test_new_readers_return_nothing_for_a_family_without_the_mixer():
    """In a cell of another family (no ``short_conv_fwd_cost``, no such
    group) the roofline reader finds nothing to read."""
    class Run:
        trace = False
        counters = {"per_chip_batch": 1}

    run = Run()
    run.cell = manifest.Cell(manifest.load_manifest(), "qwen3next-train-1chip")
    for name in NEW_READERS:
        assert manifest.load_reader(name).compute(run) is None


def test_roofline_reader_divides_the_bound_by_the_forward_group(cell,
                                                                monkeypatch):
    reader = manifest.load_reader("short_conv_fwd_roofline")

    class Device:
        device_kind = "TPU v5 lite"

    class Run:
        trace = True
        counters = {"per_chip_batch": 4}
        devices = [Device()]

    run = Run()
    run.cell = cell
    least, which = reader.bound(run)
    assert which == "memory" and round(least * 1e3, 2) == 2.62
    run._scope_reduction = {"groups_ms": {"short_conv_fwd": 10.0,
                                          "short_conv_bwd": 30.0}}
    assert reader.compute(run) == pytest.approx(26.2, abs=0.05)
    assert manifest.load_reader("short_conv_ms.train").compute(run) == 40.0
    run._scope_reduction = {"groups_ms": {"short_conv_fwd": 0.0}}
    assert reader.compute(run) is None
    assert manifest.load_reader("short_conv_ms.train").compute(run) is None


_OP_NAME = re.compile(r'op_name="([^"]+)"')


def test_groups_follow_the_other_hybrids_order():
    """The rules of scope_groups/qwen3_next.json in the same order, with the
    new mixers' groups in place of the delta rule's, the dense feed-forward's
    in place of the shared expert's: a group means the same in every cell."""
    mine = manifest.load_json(scope_reduce.groups_file("lfm2_moe"))
    other = manifest.load_json(scope_reduce.groups_file("qwen3_next"))
    swap = {"gdn_scan_fwd": "short_conv_fwd", "gdn_scan_bwd": "short_conv_bwd",
            "moe_shared": "dense_ffn", "gated_attn": "gqa_attn"}
    expected = [swap.get(r["group"], r["group"]) for r in other["rules"]
                if r["group"] != "gdn_conv"]
    assert [r["group"] for r in mine["rules"]] == expected
    kept = {r["group"]: (r["path"], r.get("op")) for r in other["rules"]}
    for r in mine["rules"]:
        if r["group"] in kept and r["group"] not in ("embed", "head_loss"):
            assert (r["path"], r.get("op")) == kept[r["group"]], r["group"]
    assert mine["program_scope"] == other["program_scope"]
    assert mine["scopes"] == other["scopes"]


def test_groups_name_every_scope_and_leave_little_unnamed():
    """The groups file against the program: every scope the model enters has
    a group of its own, and of the compiled rehearsal step's operations that
    carry a scope path only a few fall to `unnamed`."""
    import horovod_tpu.jax as hvd
    import jax.numpy as jnp
    from horovod_tpu import trace

    groups = scope_reduce.Groups("lfm2_moe")
    doc = manifest.load_json(scope_reduce.groups_file("lfm2_moe"))
    assert tuple(doc["model_scopes"]) == trace.LFM2_SCOPES
    for scope in trace.LFM2_SCOPES + (trace.SCOPE_FLASH_BWD,):
        assert any(p.search(f"jit(step)/x/{scope}/dot_general")
                   for _, p, _ in groups.rules), scope
    top = "jit(step)/hvd_loss_grad/"
    for opcode, path, group in [
        ("fusion", top + "jvp(Lfm2MoeLM)/layer_0/conv/short_conv/mul",
         "short_conv_fwd"),
        ("fusion", top + "transpose(jvp(Lfm2MoeLM))/layer_0/conv/short_conv/"
         "mul", "short_conv_bwd"),
        ("fusion", top + "jvp(Lfm2MoeLM)/layer_0/conv/in_proj/dot_general",
         "blocks_fwd"),
        ("fusion", top + "jvp(Lfm2MoeLM)/layer_0/feed_forward/w1/dot_general",
         "dense_ffn"),
        ("fusion", top + "jvp(Lfm2MoeLM)/layer_2/feed_forward/moe_route/sort",
         "moe_route"),
        ("fusion", top + "jvp(Lfm2MoeLM)/layer_1/self_attn/gqa_attn/q_proj/"
         "dot_general", "gqa_attn"),
        ("custom-call", top + "jvp(Lfm2MoeLM)/layer_1/self_attn/gqa_attn/"
         "attention/pallas_call", "attn_fwd"),
        # the tied head's products read as the head's, not the table's
        ("fusion", top + "jvp(Lfm2MoeLM)/lm_head/dot_general", "head_loss"),
        ("fusion", top + "transpose(jvp(Lfm2MoeLM))/lm_head/dot_general",
         "head_loss"),
        ("fusion", top + "jvp(Lfm2MoeLM)/embed_tokens/take", "embed"),
    ]:
        assert scope_reduce.group_of(groups.rules, opcode, path) == group, path
    # moe_experts_roofline finds the backward's beginning by the head's
    # transposed product: the scope lm_head gives it that path here too
    reader = manifest.load_reader("moe_experts_roofline")
    assert reader.BACKWARD.search(
        top + "transpose(jvp(Lfm2MoeLM))/lm_head/dot_general")

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
        # a reducer's or a comparator's scalar body carries the bare
        # primitive's name (`reduce_sum`, `sort`): it is part of the
        # operation that calls it, never an event of its own
        if not path or " = " not in line or "/" not in path.group(1):
            continue
        opcode = scope_reduce._OPCODE.search(line.partition(" = ")[2])
        group = scope_reduce.group_of(groups.rules,
                                      opcode.group(1) if opcode else "",
                                      path.group(1))
        seen[group] = seen.get(group, 0) + 1
    total = sum(seen.values())
    assert seen.get(scope_reduce.UNNAMED, 0) < 0.02 * total, seen
    for group in ("short_conv_fwd", "short_conv_bwd", "moe_experts_fwd",
                  "moe_experts_bwd", "moe_route", "dense_ffn", "gqa_attn",
                  "attn_bwd", "head_loss", "embed", "blocks_fwd",
                  "blocks_bwd", "optimizer"):
        assert seen.get(group, 0) > 0, (group, seen)
