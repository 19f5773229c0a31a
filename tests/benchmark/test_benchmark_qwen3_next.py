"""The benchmark's additions for ``qwen3-next-80b-a3b-ep16``: the counts of the
issue's sizing table from ``families/qwen3_next.py``, the configuration file
against the catalog's published ``config.json``, the manifest's entries, and
the scope groups against the program's own scopes."""

import json
import os
import re

import jax
import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import manifest, scope_reduce, weights
from benchmark.families import qwen3_next as family

CELL = "qwen3next-train-1chip"
CONFIG = "qwen3-next-80b-a3b-ep16"
# The published config.json (the catalog's row beside the model-configs guide).
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


_count = weights.count


def test_parameter_counts_of_the_sizing_table(cell):
    spec = family.param_spec(cell.config)
    mega = lambda tree: round(_count(tree) / 1e6, 2)
    gdn, attn = spec["layer_0"], spec["layer_3"]
    assert mega(gdn["linear_attn"]) == 33.72
    assert mega(attn["self_attn"]) == 27.26
    beside = {k: v for k, v in gdn["mlp"].items() if k != "experts"}
    assert mega([beside, gdn["input_norm"], gdn["post_norm"]]) == 4.20
    assert mega(gdn["mlp"]["experts"]) == 100.66
    layers = sum(_count(spec[f"layer_{i}"]) for i in range(4))
    assert round(layers / 1e6, 1) == 547.9
    ends = [spec["embed_tokens"], spec["lm_head"], spec["norm"]]
    assert round(_count(ends) / 1e6, 1) == 77.8
    total = _count(spec)
    assert total == 625_667_136 and round(total / 1e6, 1) == 625.7
    # f32 parameters, gradients and AdamW's two moments: 16 bytes each
    assert round(total * 16 / 1e9, 1) == 10.0


def test_layer_pattern_is_one_whole_period(cell):
    kinds = ["self_attn" if family.is_attention(cell.config, i)
             else "linear_attn" for i in range(4)]
    assert kinds == ["linear_attn"] * 3 + ["self_attn"]
    spec = family.param_spec(cell.config)
    assert all(k in spec[f"layer_{i}"] for i, k in enumerate(kinds))


def test_operation_counts(cell):
    cfg, traffic = cell.config, cell.traffic
    assert family.expected_held_per_token(cfg) == 0.625
    # 6 x 192 M multiplied weights a token x 8192: about 9.4 TFLOP
    per_token = family.matmul_params_per_token(cfg)
    assert round(per_token / 1e6) == 192
    assert round(6 * per_token * 8192 / 1e12, 1) == 9.4
    ops, nbytes = family.attn_fwd_cost(cfg, traffic, 1)
    assert round(3 * ops / 1e12, 2) == 1.65        # forward and backward
    assert nbytes == 4 * 8192 * 4096 * 2
    total = family.train_ops_per_step(cfg, traffic, 1)
    assert 11.0e12 < total < 11.7e12
    # the delta rule: about 7 x 128 x 128 a token a head, memory-bound on a
    # v5e at about 0.33 ms a layer
    ops, nbytes = family.gdn_fwd_cost(cfg, traffic, 1)
    assert ops == 3 * 8192 * 32 * 7 * 128 * 128
    peak = manifest.peak_for("TPU v5 lite")
    by_bytes = nbytes / peak["hbm_bytes_per_s"] / 3
    assert by_bytes > ops / peak["bf16_flops"] / 3
    assert 0.30e-3 < by_bytes < 0.36e-3
    # the experts: 5120 expected pairs a layer, 201 MB of weights a layer
    ops, nbytes = family.moe_experts_cost(cfg, traffic, 1)
    assert ops == 4 * 5120 * 6 * 2048 * 512
    assert round(32 * 3 * 2048 * 512 * 2 / 1e6) == 201
    assert nbytes == 4 * (32 * 3 * 2048 * 512 * 2 + 5120 * 2 * 2048 * 2)
    assert nbytes / peak["hbm_bytes_per_s"] > ops / peak["bf16_flops"]


def test_configuration_file_states_every_published_size(cell):
    cfg = cell.config
    entry = next(c for c in cell.manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 32, 18992)
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 16 == cfg["num_experts_routed"] == 512
    assert "16 chips share each layer" in cfg["deployment"]
    assert cfg["departures"] and cfg["assumed"]
    assert cfg["source"] == entry["source"]
    assert cfg["train"]["stated_precision"] == "bfloat16"
    assert cfg["train"]["control_precision"] == "int8"


def test_manifest_entries_of_the_cell(cell):
    # the entry is there, with these keys; where it stands in the list, and
    # what later PRs appended behind it, is not this test's to hold
    assert cell.manifest["workloads"].count(cell.entry) == 1
    assert cell.entry == {
        "name": CELL, "config": CONFIG, "traffic": "lm-train-t8192",
        "chips": 1, "why": cell.entry["why"]}
    assert cell.chips == 1 and cell.options["mesh"] == {"data": 1}
    assert len(cell.entry["why"]) <= 200
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["per_chip_batch"] == 1
    assert {"train_samples_per_s_per_chip", "setup_s"} <= {
        m["name"] for m in cell.end_to_end()}
    names = {m["name"] for m in cell.per_layer()}
    assert {"gdn_ms.train", "gdn_fwd_roofline", "moe_route_ms.train",
            "moe_experts_ms.train", "moe_experts_roofline",
            "attn_fwd_roofline", "attn_bwd_ms.train", "head_loss_ms.train",
            "optimizer_ms.train", "mfu.train",
            "scope_unnamed_share.train"} <= names
    assert not any(n.startswith("grad_") for n in names)   # no wire here
    for name in names:
        assert hasattr(manifest.load_reader(name), "compute")
    # the cells that were there are there still
    assert {"gpt2m-train-1chip", "gpt2m-train-dp4"} <= {
        w["name"] for w in cell.manifest["workloads"]}
    limits = cell.options["limits"]
    assert set(limits) == {"loss_rel", "first_grad_norm", "update_norm",
                           "nonfinite_losses"}
    assert "calibrate" in cell.options["limits_set_from"]


def test_new_readers_return_nothing_without_a_trace(cell):
    """On a run with no device trace (and on a parent whose step has no such
    scope) the new readers return None and do not raise."""
    class Run:
        trace = False
        counters = {"per_chip_batch": 1}

    run = Run()
    run.cell = cell
    for name in ("gdn_ms.train", "gdn_fwd_roofline", "moe_route_ms.train",
                 "moe_experts_ms.train", "moe_experts_roofline"):
        assert manifest.load_reader(name).compute(run) is None


def test_forward_kernels_are_those_before_the_heads_transposed_product():
    reader = manifest.load_reader("moe_experts_roofline")
    paths = {
        "convert.1": "jit(step)/hvd_loss_grad/transpose(jvp(M))/layer_0/x",
        "fusion.9": "jit(step)/hvd_loss_grad/transpose(jvp(M))/lm_head/dot",
        "ragged-dot-none": "ragged-dot-none",
        "ragged-dot-none.1": "ragged-dot-none",
        "ragged-dot-none.2": "ragged-dot-none",
    }
    ops = [["convert.1", 0, 5],              # a cast the compiler moved ahead
           ["ragged-dot-none", 10, 7], ["ragged-dot-none.1", 20, 8],
           ["fusion.9", 100, 50],            # the backward begins
           ["ragged-dot-none.2", 200, 9]]    # recomputed: not the forward's
    assert reader.forward_kernels_ns(ops, paths) == 15
    assert reader.forward_kernels_ns(ops[:3], paths) is None


_OP_NAME = re.compile(r'op_name="([^"]+)"')


def test_groups_name_every_scope_and_leave_little_unnamed():
    """The groups file against the program: every scope the model enters has
    a group of its own, and of the compiled rehearsal step's operations that
    carry a scope path only a few fall to `unnamed`."""
    import horovod_tpu.jax as hvd
    import jax.numpy as jnp
    from horovod_tpu import trace

    groups = scope_reduce.Groups("qwen3_next")
    doc = manifest.load_json(scope_reduce.groups_file("qwen3_next"))
    assert tuple(doc["model_scopes"]) == trace.MODEL_SCOPES
    for scope in trace.MODEL_SCOPES + (trace.SCOPE_FLASH_BWD,):
        assert any(p.search(f"jit(step)/x/{scope}/dot_general")
                   for _, p, _ in groups.rules), scope

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
    for group in ("gdn_conv", "gdn_scan_fwd", "gdn_scan_bwd",
                  "moe_experts_fwd", "moe_experts_bwd", "moe_route",
                  "moe_shared", "gated_attn", "attn_bwd", "head_loss",
                  "embed", "blocks_fwd", "blocks_bwd", "optimizer"):
        assert seen.get(group, 0) > 0, (group, seen)
