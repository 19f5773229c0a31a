"""The benchmark's additions for ``nemotron-twotower-30b-a3b-ep16``: the
counts of the issue's sizing table from ``families/nemotron_h.py``, the
configuration file against the catalog's published ``config.json``, the
manifest's entries with seven cells, the three new readers, and the scope
groups against the program's own scopes; the cell's rehearsal end to end."""

import re

import jax
import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from bench_helpers import CONTRACT_KEYS, rehearse
from benchmark import manifest, scope_reduce, weights
from benchmark.families import nemotron_h as family

CELL = "nemotronh-train-1chip"
CONFIG = "nemotron-twotower-30b-a3b-ep16"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# The published config.json (the catalog's row beside the model-configs guide).
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688, "hybrid_override_pattern": PATTERN,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072,
}
NEW_READERS = ("ssm_ms.train", "ssm_scan_ms.train", "ssm_fwd_roofline")
SHARED_READERS = ("attn_fwd_roofline", "attn_bwd_ms.train",
                  "attn_bwd_roofline", "head_loss_ms.train",
                  "optimizer_ms.train", "scope_unnamed_share.train",
                  "moe_route_ms.train", "moe_experts_ms.train",
                  "moe_experts_roofline", "moe_route_kernel_ms.train")


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


_count = weights.count


def test_parameter_counts_of_the_sizing_table(cell):
    """The issue's table against the parameter tree, to the parameter."""
    spec = family.param_spec(cell.config)
    layers = [_count(spec[f"layer_{i}"]) for i in range(9)]
    by_kind = dict(zip(cell.config["hybrid_override_pattern"], layers))
    assert by_kind == {"M": 38_744_896, "*": 23_399_040, "E": 100_125_440}
    assert layers == [by_kind[k] for k in "MEMEM*EME"]
    expert = spec["layer_1"]["mixer"]
    assert _count(expert["router"]) == 344_064
    assert _count(expert["expert_bias"]) == 128
    assert _count(expert["shared_up_proj"]) + _count(
        expert["shared_down_proj"]) == 19_955_712
    assert _count(expert["experts"]) == 8 * 9_977_856
    assert sorted(expert["experts"]) == ["down", "up"]       # no gate
    assert expert["expert_bias"] == weights.Leaf((128,), "normal", 0.005)
    # the experts' second matrices alone are drawn narrower (the
    # configuration's file says why); their first keep initializer_range
    assert (expert["experts"]["down"].std, expert["experts"]["up"].std,
            expert["shared_down_proj"]["kernel"].std,
            expert["shared_up_proj"]["kernel"].std) == (
        0.00125, 0.02, 0.00125, 0.02)
    mamba = spec["layer_0"]["mixer"]
    assert mamba["in_proj"]["kernel"].shape == (2688, 4096 + 6144 + 64)
    assert mamba["conv"]["kernel"].shape == (4, 6144)
    assert mamba["conv"]["bias"] == weights.Leaf((6144,), "zeros")
    assert (mamba["A_log"].kind, mamba["dt_bias"].kind,
            mamba["D"].kind) == ("zeros", "zeros", "ones")
    assert mamba["norm"]["scale"].shape == (4096,)
    assert 4 * by_kind["M"] == 154_979_584
    assert 4 * by_kind["E"] == 400_501_760
    head = (_count(spec["embed_tokens"]) + _count(spec["lm_head"])
            + _count(spec["norm_f"]))
    assert head == 2 * 16384 * 2688 + 2688 == 88_083_072
    total = _count(spec)
    assert total == 666_963_456 == cell.config["parameters"]
    assert total == sum(layers) + head
    # f32 parameters, gradients and AdamW's two moments: 16 bytes each
    assert round(total * 16 / 1e9, 2) == 10.67
    # whole, the layers are the row's 30B-A3B: 23 Mamba-2, 23 expert and 6
    # attention layers with all 128 experts; 6 experts a token
    whole = (23 * by_kind["M"] + 6 * by_kind["*"]
             + 23 * (by_kind["E"] + 120 * 9_977_856))
    assert round(whole / 1e9, 1) == 30.9
    assert round((whole + 2 * 131072 * 2688) / 1e9, 1) == 31.6
    active = (23 * by_kind["M"] + 6 * by_kind["*"]
              + 23 * (by_kind["E"] - 2 * 9_977_856))
    assert round(active / 1e9, 1) == 2.9


def test_layer_pattern_is_the_first_nine_published_layers(cell):
    cfg = cell.config
    assert cfg["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert cfg["num_hidden_layers"] == 9
    assert (PATTERN.count("M"), PATTERN.count("E"),
            PATTERN.count("*")) == (23, 23, 6)
    stars = [i for i, k in enumerate(PATTERN) if k == "*"]
    assert [b - a for a, b in zip([-1] + stars, stars)] == [6, 7, 7, 7, 7, 9]
    m = family.dims(cfg)
    assert (m["mamba_layers"], m["expert_layers"],
            m["attn_layers"]) == (4, 4, 1)
    assert m["inner"] == 4096 != cfg["expand"] * cfg["hidden_size"]
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.dims({**cfg, "num_hidden_layers": 10})
    with pytest.raises(ValueError, match="group limit"):
        family.model_config({**cfg, "n_group": 2})


def test_operation_counts(cell):
    cfg, traffic = cell.config, cell.traffic
    assert family.expected_held_per_token(cfg) == 0.375
    # 6 x 318.4 M multiplied weights a token x 16384 + attention + scans
    per_token = family.matmul_params_per_token(cfg)
    assert round(per_token / 1e6, 1) == 318.4
    assert per_token == (
        4 * (2688 * 10304 + 4096 * 2688) + (2 * 2688 * 4096 + 2 * 2688 * 256)
        + 4 * (2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856)
        + 2688 * 16384)
    attn, nbytes = family.attn_fwd_cost(cfg, traffic, 2)
    assert round(3 * attn / 1e12, 1) == 3.3     # forward and backward
    assert nbytes == 4 * 2 * 8192 * 4096 * 2
    assert family.attn_fwd_calls(cfg) == 1
    scan, scan_bytes = family.ssd_fwd_cost(cfg, traffic, 2)
    total = family.train_ops_per_step(cfg, traffic, 2)
    assert total == 6 * per_token * 16384 + 3 * attn + 3 * scan
    assert round(total / 1e12, 1) == 35.1
    # the Mamba-2 projections alone: 43% of the step's operations
    mamba = 6 * 4 * (2688 * 10304 + 4096 * 2688) * 16384
    assert round(mamba / 1e12, 1) == 15.2 and round(100 * mamba / total) == 43
    # the held experts: TWO matrices an expert, 4 d f operations a pair; a
    # held expert sees 768 tokens a layer (an eighth of EP16's 6144)
    ops, nbytes = family.moe_experts_cost(cfg, traffic, 2)
    assert ops == 4 * (16384 * 0.375) * 4 * 2688 * 1856
    assert nbytes == 4 * (8 * 2 * 2688 * 1856 * 2 + 6144 * 2 * 2688 * 2)
    assert 2 * 8192 * 6 // 128 == 768 and 16 * 8192 * 6 // 128 == 6144
    peak = manifest.peak_for("TPU v5 lite")
    assert ops / peak["bf16_flops"] > nbytes / peak["hbm_bytes_per_s"]


def test_scan_cost_against_a_hand_worked_shape(cell):
    """One layer, 10 tokens, 2 heads of 4 over a state of 8, one group: per
    token and head 5 * 4 * 8 = 160 operations of the recurrence (decay 32,
    update 64, S C 64) and 8 of D x; x and y 8 values, B and C 8 each in two
    bytes, dt two floats."""
    tiny = {**cell.config, "hybrid_override_pattern": "M",
            "num_hidden_layers": 1, "mamba_num_heads": 2, "mamba_head_dim": 4,
            "ssm_state_size": 8, "n_groups": 1}
    ops, nbytes = family.ssd_fwd_cost(tiny, {"seq_len": 10}, 1)
    assert ops == 10 * 2 * (160 + 8) == 3360
    assert nbytes == 10 * ((8 + 8 + 8 + 8) * 2 + 2 * 4) == 720
    # at the cell's shapes: 172 GFLOP and 1.36 GB a step, memory-bound
    ops, nbytes = family.ssd_fwd_cost(cell.config, cell.traffic, 2)
    assert ops == 4 * 16384 * 64 * (5 * 64 * 128 + 2 * 64)
    assert nbytes == 4 * 16384 * ((4096 + 1024 + 1024 + 4096) * 2 + 64 * 4)
    peak = manifest.peak_for("TPU v5 lite")
    assert nbytes / peak["hbm_bytes_per_s"] > ops / peak["bf16_flops"]


def test_configuration_file_states_every_published_size(cell):
    cfg = cell.config
    entry = next(c for c in cell.manifest["configs"] if c["name"] == CONFIG)
    assert cell.manifest["configs"].count(entry) == 1
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, 8, 16384)
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["n_routed_experts"] * 16 == cfg["n_routed_experts_routed"] == 128
    assert cfg["first_expert_held"] == 0
    assert "sixteen chips share each layer" in cfg["deployment"]
    assert "layers 0 to 8" in cfg["deployment"]
    said = " ".join(cfg["departures"] + cfg["assumed"])
    for phrase in ("denoiser", "adaLN", "block-diffusion", "no rotary",
                   "load-balancing", "1 - 1e-9", "512 channels",
                   "A_log and dt_bias zeros", "rescale_prenorm_residual",
                   "route_norm_eps", "AdamW 1e-5", "seeded_expert_down_std"):
        assert phrase in said, phrase
    assert "[z | x | B | C | dt]" in cfg["layout"]
    assert cfg["source"] == entry["source"]
    assert cfg["train"]["stated_precision"] == "bfloat16"
    assert cfg["train"]["control_precision"] == "int8"
    assert cfg["train"]["remat"] is True
    assert cfg["train"]["optimizer"]["learning_rate"] == 1e-5
    model = family.model_config(cfg)
    assert (model.n_layers, model.pattern, model.experts_held,
            model.chunk, model.shared_dim) == (9, "MEMEM*EME", 8, 128, 3712)


def test_manifest_entries_of_the_cell(cell):
    # the entry is there, with these keys; where it stands in the list, and
    # what later PRs append behind it, is not this test's to hold
    assert cell.manifest["workloads"].count(cell.entry) == 1
    assert cell.entry == {
        "name": CELL, "config": CONFIG, "traffic": "lm-train-t8192-b2",
        "chips": 1, "why": cell.entry["why"]}
    # the six cells that were there are there still: seven, one on four chips
    names = [w["name"] for w in cell.manifest["workloads"]]
    assert {"gpt2m-train-1chip", "gpt2m-train-dp4", "qwen3next-train-1chip",
            "lfm2moe-train-1chip", "xing4-train-1chip",
            "keyevl-train-1chip", CELL} <= set(names)
    assert len(names) >= 7
    assert [w["name"] for w in cell.manifest["workloads"]
            if w["chips"] == 4] == ["gpt2m-train-dp4"]
    assert cell.chips == 1 and cell.options["mesh"] == {"data": 1}
    assert cell.options["step_options"] == {}
    assert len(cell.entry["why"]) <= 200
    entry = next(c for c in cell.manifest["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    assert (cell.traffic["seq_len"],
            cell.traffic["per_chip_batch"]) == (8192, 2)
    assert (cell.traffic["kind"], cell.traffic["pool_batches"],
            cell.traffic["fetch_every"], cell.traffic["check_steps"],
            cell.traffic["warm_steps"], cell.traffic["trace_seconds"]) == (
        "train_steps", 16, 10, 3, 2, 4)
    assert {"train_samples_per_s_per_chip", "setup_s"} <= {
        m["name"] for m in cell.end_to_end()}
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW_READERS) | set(SHARED_READERS) | {
        "mfu.train", "step_device_ms.train", "dispatch_ms.train",
        "device_idle_share.train", "peak_hbm_gb.train",
        "kernel_fallbacks.train", "compiles_in_window"} <= mine
    for name in mine:
        assert hasattr(manifest.load_reader(name), "compute")
    # the three new metrics are declared for this cell alone, on one layer
    declared = {m["name"]: m for m in cell.manifest["per_layer"]}
    new = [declared[name] for name in NEW_READERS]
    assert all(m["workloads"] == [CELL] and m["layer"] == "State space"
               and m["moves"] == "train_samples_per_s_per_chip"
               and m["source"] == "device_trace" for m in new)
    assert [m["unit"] for m in new] == ["ms", "ms", "%"]
    # and the shared readers list it beside the cells that were there
    for name in SHARED_READERS:
        assert {CELL, "keyevl-train-1chip"} <= set(
            declared[name]["workloads"]), name
    # the delta rule's, the short convolution's, the stream mixes' and the
    # indexer's metrics are not this cell's
    assert not any(n.startswith(("gdn_", "short_conv", "hc_mix",
                                 "sparse_index", "latent_", "grad_"))
                   for n in mine)
    limits = cell.options["limits"]
    assert set(limits) == {"loss_rel", "first_grad_norm", "update_norm",
                           "nonfinite_losses"}
    assert "calibrate" in cell.options["limits_set_from"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_where_there_is_nothing(cell, name):
    """No device trace, or a cell of another family (no ``ssd_fwd_cost``, no
    such group; a parent whose step has no such scope): ``None``, no
    raise."""
    class Run:
        trace = False
        counters = {"per_chip_batch": 2}

    run = Run()
    run.cell = cell
    assert manifest.load_reader(name).compute(run) is None
    other = Run()
    other.cell = manifest.Cell(manifest.load_manifest(),
                               "qwen3next-train-1chip")
    other._scope_reduction = {"groups_ms": {"gdn_scan_fwd": 3.0}}
    assert manifest.load_reader(name).compute(other) is None


def test_readers_read_their_groups(cell):
    class Device:
        device_kind = "TPU v5 lite"

    class Run:
        trace = True
        counters = {"per_chip_batch": 2}
        devices = [Device()]

    run = Run()
    run.cell = cell
    roofline = manifest.load_reader("ssm_fwd_roofline")
    least, which = roofline.bound(run)
    assert which == "memory" and round(least * 1e3, 2) == 1.66
    run._scope_reduction = {"groups_ms": {
        "ssm_conv": 7.0, "ssm_scan_fwd": 20.0, "ssm_scan_bwd": 50.0,
        "ssm_mixer": 100.0}}
    assert roofline.compute(run) == pytest.approx(8.3, abs=0.05)
    assert manifest.load_reader("ssm_ms.train").compute(run) == 77.0
    assert manifest.load_reader("ssm_scan_ms.train").compute(run) == 70.0
    # the share cannot pass 100% while the forward takes its least time
    run._scope_reduction = {"groups_ms": {"ssm_scan_fwd": least * 1e3}}
    assert roofline.compute(run) == pytest.approx(100.0)
    run._scope_reduction = {"groups_ms": {"ssm_scan_fwd": 0.0}}
    assert roofline.compute(run) is None
    assert manifest.load_reader("ssm_ms.train").compute(run) is None
    assert manifest.load_reader("ssm_scan_ms.train").compute(run) is None


_OP_NAME = re.compile(r'op_name="([^"]+)"')


def test_groups_name_every_scope_and_leave_little_unnamed():
    """The groups file against the program: every scope the model enters has
    a group of its own, the convolution's and the scan's groups come before
    the mixer's that holds them, and of the compiled rehearsal step's
    operations that carry a scope path only a few fall to `unnamed`."""
    import horovod_tpu.jax as hvd
    import jax.numpy as jnp
    from horovod_tpu import trace

    groups = scope_reduce.Groups("nemotron_h")
    doc = manifest.load_json(scope_reduce.groups_file("nemotron_h"))
    other = manifest.load_json(scope_reduce.groups_file("lfm2_moe"))
    assert tuple(doc["model_scopes"]) == trace.NEMOTRON_H_SCOPES
    assert doc["program_scope"] == other["program_scope"]
    assert doc["scopes"] == other["scopes"]
    kept = {r["group"]: (r["path"], r.get("op")) for r in other["rules"]}
    for r in doc["rules"]:
        if r["group"] in kept and r["group"] not in (
                "embed", "head_loss", "blocks_fwd", "blocks_bwd"):
            assert (r["path"], r.get("op")) == kept[r["group"]], r["group"]
    order = [r["group"] for r in doc["rules"]]
    assert order.index("ssm_conv") < order.index("ssm_scan_fwd") < order.index(
        "ssm_scan_bwd") < order.index("ssm_mixer")
    assert order.index("attn_fwd") < order.index("gqa_attn")
    for scope in trace.NEMOTRON_H_SCOPES + (trace.SCOPE_FLASH_BWD,):
        assert any(p.search(f"jit(step)/x/{scope}/dot_general")
                   for _, p, _ in groups.rules), scope
    top = "jit(step)/hvd_loss_grad/"
    first, again = top + "jvp(NemotronHLM)/", top + "transpose(jvp(NemotronHLM))/"
    for opcode, path, group in [
        ("fusion", first + "layer_0/mixer/ssm_mixer/ssm_conv/mul", "ssm_conv"),
        ("fusion", again + "layer_0/mixer/ssm_mixer/ssm_conv/mul", "ssm_conv"),
        ("fusion", first + "layer_0/mixer/ssm_mixer/ssm_scan/checkpoint/"
         "dot_general", "ssm_scan_fwd"),
        ("fusion", first + "layer_0/mixer/ssm_mixer/ssm_scan/checkpoint/"
         "while/body/mul", "ssm_scan_fwd"),
        ("fusion", again + "layer_0/mixer/ssm_mixer/ssm_scan/checkpoint/"
         "rematted_computation/dot_general", "ssm_scan_bwd"),
        ("fusion", first + "layer_0/mixer/ssm_mixer/dot_general",
         "ssm_mixer"),
        ("fusion", again + "layer_0/mixer/ssm_mixer/out_proj/dot_general",
         "ssm_mixer"),
        ("fusion", first + "layer_5/mixer/gqa_attn/q_proj/dot_general",
         "gqa_attn"),
        ("custom-call", first + "layer_5/mixer/gqa_attn/attention/"
         "pallas_call", "attn_fwd"),
        ("custom-call", again + "layer_5/mixer/gqa_attn/attention/flash_bwd/"
         "pallas_call", "attn_bwd"),
        ("fusion", first + "layer_1/mixer/moe_route/sort", "moe_route"),
        ("fusion", first + "layer_1/mixer/moe_experts/square",
         "moe_experts_fwd"),
        ("fusion", again + "layer_1/mixer/moe_experts/square",
         "moe_experts_bwd"),
        ("custom-call", "ragged-dot-none", "moe_experts_kernel"),
        ("fusion", first + "layer_1/mixer/moe_shared/shared_up_proj/"
         "dot_general", "moe_shared"),
        ("fusion", first + "layer_1/norm/mul", "blocks_fwd"),
        ("fusion", again + "norm_f/mul", "blocks_bwd"),
        ("fusion", first + "lm_head/dot_general", "head_loss"),
        ("fusion", first + "embed_tokens/take", "embed"),
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
    for group in ("ssm_conv", "ssm_scan_fwd", "ssm_scan_bwd", "ssm_mixer",
                  "gqa_attn", "attn_bwd", "moe_experts_fwd",
                  "moe_experts_bwd", "moe_route", "moe_shared", "head_loss",
                  "embed", "blocks_fwd", "blocks_bwd", "optimizer"):
        assert seen.get(group, 0) > 0, (group, seen)


def test_traced_rehearsal_comes_out_correct():
    """The cell end to end on the CPU at its rehearsal sizes, the command as
    the driver gives it with ``--trace 1`` (the committed limits are the
    chip's, at full size; the rehearsal's are in the cell's file too)."""
    line, out = rehearse(CELL, seed=2147483660, seconds=0.5, trace=1,
                         timeout=600)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line) == CONTRACT_KEYS | {"breakdown"}
    assert "dispatch_ms.train" in line["metrics"]
    # no TPU plane in a CPU trace: the device readers, the new ones among
    # them, find nothing and are left out
    assert not any(name.startswith(("ssm_", "moe_", "attn_"))
                   for name in line["metrics"])
    assert '"number": "first_grad_norm"' in out
