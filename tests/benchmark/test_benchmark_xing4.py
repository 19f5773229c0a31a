"""The benchmark's additions for ``xing4.0-29b-a4b-ep8``: the issue's
parameter table from ``families/xing4.py``, the counts of operations and
bytes, the configuration file against the catalog's published ``config.json``,
the manifest's entries (by containment: where they stand in their lists, and
what later PRs append behind them, is not this file's to hold), the three new
readers, the scope groups against the program's own scopes, and the cell's
rehearsal on the CPU."""

import re

import jax
import pytest

from bench_helpers import CONTRACT_KEYS, rehearse  # first: sets sys.path
from benchmark import manifest, scope_reduce, weights
from benchmark.families import xing4 as family

CELL = "xing4-train-1chip"
CONFIG = "xing4.0-29b-a4b-ep8"
# The published config.json (the catalog's row beside the model-configs guide).
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072,
}
NEW_READERS = ("hc_mix_ms.train", "hc_mix_fwd_roofline",
               "latent_proj_ms.train")
SHARED_READERS = ("attn_fwd_roofline", "attn_bwd_roofline",
                  "attn_bwd_ms.train", "head_loss_ms.train",
                  "optimizer_ms.train", "scope_unnamed_share.train",
                  "moe_route_ms.train", "moe_route_kernel_ms.train",
                  "moe_experts_ms.train", "moe_experts_roofline")


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


_count = weights.count


def test_parameter_counts_of_the_sizing_table(cell):
    spec = family.param_spec(cell.config)
    mega = lambda tree: round(_count(tree) / 1e6, 3)
    dense, sparse = spec["layer_0"], spec["layer_1"]
    attn = dense["self_attn"]
    assert [mega(attn[k]) for k in ("q_a_proj", "q_b_proj", "kv_a_proj",
                                    "kv_b_proj", "o_proj")] == [
        2.753, 4.719, 2.064, 4.194, 14.680]
    assert round(mega(attn), 2) == 28.41           # with the two latent norms
    assert _count(dense["attn_hc"]) == _count(dense["ffn_hc"]) == 344_091
    assert dense["attn_hc"]["phi"].shape == (4 * 3584, 24)
    assert round(mega(dense["mlp"]), 2) == 99.09   # 3 x 3584 x 9216
    assert round(mega(sparse["shared_expert"]), 2) == 11.01
    assert round(mega(sparse["mlp"]["router"])
                 + mega(sparse["mlp"]["expert_bias"]), 2) == 0.23
    one = jax.tree.map(lambda l: l._replace(shape=l.shape[1:]),
                       sparse["mlp"]["experts"], is_leaf=weights.is_leaf)
    assert round(mega(one), 2) == 11.01
    assert sparse["mlp"]["experts"]["gate"].shape == (8, 3584, 1024)
    assert round(mega(sparse["mlp"]) + mega(sparse["shared_expert"]),
                 2) == 99.32
    # the issue's table gives 128.19 / 128.42 without a layer's two norms
    # (7168 weights)
    assert _count(dense) == 128_196_918 and _count(sparse) == 128_426_358
    assert all(_count(spec[f"layer_{i}"]) == _count(sparse)
               for i in (2, 3, 4))
    assert round(mega(spec["embed_tokens"]) + mega(spec["lm_head"])
                 + mega(spec["norm"]), 2) == 117.44
    total = _count(spec)
    assert total == 759_346_446 == cell.config["parameters"]
    assert round(total / 1e6, 1) == 759.3
    # f32 parameters, gradients and AdamW's two moments: 16 bytes each
    assert round(total * 16 / 1e9, 2) == 12.15
    # the drawn vectors keep their own deviations
    assert sparse["mlp"]["expert_bias"] == weights.Leaf((64,), "normal", 0.005)
    assert dense["attn_hc"]["b"] == weights.Leaf((24,), "normal", 1.0)
    assert dense["attn_hc"]["alpha"] == weights.Leaf((3,), "ones")
    assert dense["attn_hc"]["phi"].std == 0.02
    assert "shared_expert" not in dense and "router" not in dense["mlp"]


def test_operation_counts(cell):
    cfg, traffic = cell.config, cell.traffic
    assert family.expected_held_per_token(cfg) == 0.5
    per_token = family.matmul_params_per_token(cfg)
    assert round(per_token / 1e6, 1) == 370.3
    # 192 + 128 multiply-adds a pair and head over the causal half
    ops, nbytes = family.attn_fwd_cost(cfg, traffic, 1)
    assert ops == 5 * 8192 * 8192 * 32 * (192 + 128)
    assert round(ops / 5 / 1e12, 2) == 0.69        # a layer and forward pass
    assert nbytes == 5 * 8192 * 32 * (192 + 192 + 128 + 128) * 2
    assert family.attn_fwd_calls(cfg) == 5
    total = family.train_ops_per_step(cfg, traffic, 1)
    assert total == 6 * per_token * 8192 + 3 * ops
    assert round(total / 1e12, 1) == 28.5
    assert round(3 * ops / total, 2) == 0.36       # the causal scores' share
    # the held experts: 4096 expected pairs a layer (a held expert sees 512
    # tokens, an eighth of its EP8 load), 1.1 TFLOP of the step
    ops, nbytes = family.moe_experts_cost(cfg, traffic, 1)
    assert ops == 4 * 4096 * 6 * 3584 * 1024
    assert round(3 * ops / 1e12, 1) == 1.1
    assert 8192 * cfg["num_experts_per_tok"] // 64 == 512
    assert nbytes == 4 * (8 * 3 * 3584 * 1024 * 2 + 4096 * 2 * 3584 * 2)
    # the stream mixes: ten a pass, each reads and writes a 235 MB residual
    ops, nbytes = family.hc_mix_fwd_cost(cfg, traffic, 1)
    assert nbytes == 10 * 2 * (4 * 8192 * 3584 * 2)
    assert 4 * 8192 * 3584 * 2 == 234_881_024
    assert ops == 10 * 8192 * 2 * 14336 * 24
    peak = manifest.peak_for("TPU v5 lite")
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    assert round(by_bytes * 1e3, 1) == 5.7
    assert by_bytes > 10 * ops / peak["bf16_flops"]


def test_configuration_file_states_every_published_size(cell):
    cfg = cell.config
    entry = next(c for c in cell.manifest["configs"] if c["name"] == CONFIG)
    assert cell.manifest["configs"].count(entry) == 1
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 8, 16384, 0)
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["n_routed_experts"] * 8 == cfg["n_routed_experts_routed"] == 64
    assert cfg["first_expert_held"] == 0
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                "num_attention_heads"):
        assert key not in cfg["reduced"] and cfg[key] == PUBLISHED[key]
    assert "eight chips share each layer" in cfg["deployment"]
    assert "35 layers" in cfg["deployment"]
    assert "multi-token-prediction" in cfg["deployment"]
    for word in ("rows before columns", "SUMMED", "phi normal(0, 0.02)",
                 "initializer_range", "route_norm_eps", "expert_bias_std",
                 "AdamW 1e-5"):
        assert any(word in a for a in cfg["assumed"]), word
    assert any("balancing update" in d for d in cfg["departures"])
    assert any("num_nextn_predict_layers" in d for d in cfg["departures"])
    assert "HALVES" in cfg["layout"]
    assert cfg["source"] == entry["source"]
    assert cfg["train"]["stated_precision"] == "bfloat16"
    assert cfg["train"]["control_precision"] == "int8"
    assert cfg["train"]["remat"] is True
    assert cfg["train"]["optimizer"]["learning_rate"] == 1e-5
    model = family.model_config(cfg)
    assert (model.n_layers, model.n_dense_layers, model.experts_held,
            model.n_experts, model.hc_mult) == (5, 1, 8, 64, 4)
    assert model.softmax_scale() == pytest.approx(0.14468, abs=5e-6)
    with pytest.raises(ValueError, match="YaRN"):
        family.model_config({**cfg, "scoring_func": "softmax"})


def test_manifest_entries_of_the_cell(cell):
    assert cell.manifest["workloads"].count(cell.entry) == 1
    assert cell.entry == {
        "name": CELL, "config": CONFIG, "traffic": "lm-train-t8192",
        "chips": 1, "why": cell.entry["why"]}
    assert len(cell.entry["why"]) <= 200
    assert "512 tokens" in cell.entry["why"]       # how near the EP8 load
    # the cells that were there are there still
    assert {"gpt2m-train-1chip", "gpt2m-train-dp4", "qwen3next-train-1chip",
            "lfm2moe-train-1chip"} <= {
        w["name"] for w in cell.manifest["workloads"]}
    assert cell.chips == 1 and cell.options["mesh"] == {"data": 1}
    assert cell.options["step_options"] == {}
    assert (cell.traffic["seq_len"],
            cell.traffic["per_chip_batch"]) == (8192, 1)
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
               and m["moves"] == "train_samples_per_s_per_chip" for m in new)
    assert [m["layer"] for m in new] == ["Stream mixes", "Stream mixes",
                                         "Latent attention"]
    assert [(m["unit"], m["better"]) for m in new] == [
        ("ms", "lower"), ("%", "higher"), ("ms", "lower")]
    for name in SHARED_READERS:
        assert CELL in declared[name]["workloads"], name
    limits = cell.options["limits"]
    assert set(limits) == {"loss_rel", "first_grad_norm", "update_norm",
                           "nonfinite_losses"}
    assert "calibrate" in cell.options["limits_set_from"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_where_there_is_nothing(cell, name):
    """No device trace, or a cell of another family (no ``hc_mix_fwd_cost``,
    no such group; a parent whose step has no such scope): ``None``, no
    raise."""
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
    roofline = manifest.load_reader("hc_mix_fwd_roofline")
    least, which = roofline.bound(run)
    assert which == "memory" and round(least * 1e3, 2) == 5.74
    run._scope_reduction = {"groups_ms": {
        "hc_mix_fwd": 20.0, "hc_mix_bwd": 50.0, "latent_attn": 31.0}}
    assert roofline.compute(run) == pytest.approx(28.7, abs=0.05)
    assert manifest.load_reader("hc_mix_ms.train").compute(run) == 70.0
    assert manifest.load_reader("latent_proj_ms.train").compute(run) == 31.0
    run._scope_reduction = {"groups_ms": {"hc_mix_fwd": 0.0}}
    assert roofline.compute(run) is None
    assert manifest.load_reader("hc_mix_ms.train").compute(run) is None


_OP_NAME = re.compile(r'op_name="([^"]+)"')


def test_groups_name_every_scope_and_leave_little_unnamed():
    """The groups file against the program: every scope the model enters has
    a group of its own, the kernels' groups come before the mixer's that
    holds them, and of the compiled rehearsal step's operations that carry a
    scope path only a few fall to `unnamed`."""
    import horovod_tpu.jax as hvd
    import jax.numpy as jnp
    from horovod_tpu import trace

    groups = scope_reduce.Groups("xing4")
    doc = manifest.load_json(scope_reduce.groups_file("xing4"))
    other = manifest.load_json(scope_reduce.groups_file("qwen3_next"))
    assert tuple(doc["model_scopes"]) == trace.XING4_SCOPES
    assert doc["program_scope"] == other["program_scope"]
    assert doc["scopes"] == other["scopes"]
    kept = {r["group"]: (r["path"], r.get("op")) for r in other["rules"]}
    for r in doc["rules"]:
        if r["group"] in kept and r["group"] not in ("embed", "head_loss"):
            assert (r["path"], r.get("op")) == kept[r["group"]], r["group"]
    for scope in trace.XING4_SCOPES + (trace.SCOPE_FLASH_BWD,):
        assert any(p.search(f"jit(step)/x/{scope}/dot_general")
                   for _, p, _ in groups.rules), scope
    top = "jit(step)/hvd_loss_grad/"
    for opcode, path, group in [
        ("fusion", top + "jvp(Xing4LM)/layer_0/attn_hc/hc_mix/mul",
         "hc_mix_fwd"),
        ("fusion", top + "transpose(jvp(Xing4LM))/layer_0/ffn_hc/hc_mix/mul",
         "hc_mix_bwd"),
        ("fusion", top + "jvp(Xing4LM)/layer_1/self_attn/latent_attn/"
         "q_b_proj/dot_general", "latent_attn"),
        ("custom-call", top + "jvp(Xing4LM)/layer_1/self_attn/latent_attn/"
         "attention/pallas_call", "attn_fwd"),
        ("custom-call", top + "transpose(jvp(Xing4LM))/layer_1/self_attn/"
         "latent_attn/attention/flash_bwd/pallas_call", "attn_bwd"),
        ("fusion", top + "jvp(Xing4LM)/layer_0/mlp/w1/dot_general",
         "dense_ffn"),
        ("fusion", top + "jvp(Xing4LM)/layer_2/mlp/moe_route/sort",
         "moe_route"),
        ("fusion", top + "jvp(Xing4LM)/layer_2/moe_shared/shared_expert/w1/"
         "dot_general", "moe_shared"),
        ("fusion", top + "jvp(Xing4LM)/layer_2/input_layernorm/mul",
         "blocks_fwd"),
        ("fusion", top + "jvp(Xing4LM)/lm_head/dot_general", "head_loss"),
        ("fusion", top + "jvp(Xing4LM)/embed_tokens/take", "embed"),
    ]:
        assert scope_reduce.group_of(groups.rules, opcode, path) == group, path
    # moe_experts_roofline finds the backward's beginning by the head's
    # transposed product
    reader = manifest.load_reader("moe_experts_roofline")
    assert reader.BACKWARD.search(
        top + "transpose(jvp(Xing4LM))/lm_head/dot_general")

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
    for group in ("hc_mix_fwd", "hc_mix_bwd", "latent_attn", "attn_bwd",
                  "moe_experts_fwd", "moe_experts_bwd", "moe_route",
                  "moe_shared", "dense_ffn", "head_loss", "embed",
                  "blocks_fwd", "blocks_bwd", "optimizer"):
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
        assert not any(name.startswith(("hc_mix", "latent_", "moe_", "attn_"))
                       for name in line["metrics"])
    else:
        assert set(line) == CONTRACT_KEYS
        assert set(line["metrics"]) >= {"train_samples_per_s_per_chip",
                                        "setup_s"}
    assert '"number": "first_grad_norm"' in out
