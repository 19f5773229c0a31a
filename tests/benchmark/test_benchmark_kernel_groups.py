"""A Pallas kernel belongs to the scope it was written under (PR 40): for each
``scope_groups/<family>.json`` the custom calls of that family's cell, each
with the scope path the chip's trace gave it (call A of PR 40, seed
2240000001; ``layer_3`` and the like stand for every layer) and the group it
must fall in. From PR 26 to PR 39 one rule sent every ``pallas_call`` to
``attn_fwd``: the attention backward, the delta rule's kernels and the expert
layer's gather-sum read as the forward flash kernel."""

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import scope_reduce as sr

STEP = "jit(step)/hvd_loss_grad/"


def _paths(lm):
    """The four places a layer's op lies in a step with recomputation on."""
    first = STEP + f"jvp({lm})/"
    again = STEP + (f"transpose(jvp({lm}))/hvd_loss_grad/jvp({lm})/checkpoint/"
                    "rematted_computation/")
    back = STEP + f"transpose(jvp({lm}))/hvd_loss_grad/jvp({lm})/checkpoint/"
    return first, again, back


Q1, Q2, QB = _paths("Qwen3NextLM")
L1, L2, LB = _paths("Lfm2MoeLM")
G1 = STEP + "jvp(TransformerLM)/"
GB = STEP + "transpose(hvd_loss_grad)/jvp(TransformerLM)/"
CALL = "custom-call"

TABLE = {
    "gpt_dense": [
        (CALL, G1 + "block_7/attention/pallas_call", "attn_fwd"),
        (CALL, GB + "block_7/attention/flash_bwd/pallas_call", "attn_bwd"),
        # what XLA puts beside the kernels stays where it was
        ("copy", G1 + "block_7/attention/pallas_call", "blocks_fwd"),
        ("fusion", GB + "block_7/attention/flash_bwd/reduce_sum", "attn_bwd"),
        ("fusion", G1 + "block_7/attention/query/dot_general", "blocks_fwd"),
    ],
    "qwen3_next": [
        (CALL, Q1 + "layer_3/self_attn/gated_attn/attention/pallas_call",
         "attn_fwd"),
        (CALL, Q2 + "layer_3/self_attn/gated_attn/attention/pallas_call",
         "attn_fwd"),
        (CALL, QB + "layer_3/self_attn/gated_attn/attention/flash_bwd/"
         "pallas_call", "attn_bwd"),
        (CALL, Q1 + "layer_0/linear_attn/gdn_scan/gdn_fwd/pallas_call",
         "gdn_scan_fwd"),
        (CALL, Q2 + "layer_0/linear_attn/gdn_scan/gdn_fwd/pallas_call",
         "gdn_scan_bwd"),
        (CALL, QB + "layer_0/linear_attn/gdn_scan/gdn_bwd/pallas_call",
         "gdn_scan_bwd"),
        (CALL, Q1 + "layer_1/mlp/moe_route/jit(_pallas)/moe_combine/"
         "pallas_call", "moe_route"),
        (CALL, QB + "layer_1/mlp/moe_route/jit(_pallas)/moe_combine/"
         "pallas_call", "moe_route"),
        (CALL, "ragged-dot-none", "moe_experts_kernel"),
        (CALL, "ragged-dot-metadata", "moe_experts_kernel"),
        (CALL, "", sr.UNNAMED),                  # ConcatBitcast, AllocateBuffer
        ("fusion", Q1 + "layer_3/self_attn/gated_attn/dot_general",
         "gated_attn"),
        ("fusion", QB + "layer_3/self_attn/gated_attn/attention/flash_bwd/"
         "reduce_sum", "attn_bwd"),
    ],
    "lfm2_moe": [
        (CALL, L1 + "layer_1/self_attn/gqa_attn/attention/pallas_call",
         "attn_fwd"),
        (CALL, L2 + "layer_1/self_attn/gqa_attn/attention/pallas_call",
         "attn_fwd"),
        (CALL, LB + "layer_1/self_attn/gqa_attn/attention/flash_bwd/"
         "pallas_call", "attn_bwd"),
        (CALL, L1 + "layer_2/feed_forward/moe_route/jit(_pallas)/moe_combine/"
         "pallas_call", "moe_route"),
        (CALL, LB + "layer_2/feed_forward/moe_route/jit(_pallas)/moe_combine/"
         "pallas_call", "moe_route"),
        (CALL, "ragged-dot-none", "moe_experts_kernel"),
        (CALL, "ragged-dot-metadata", "moe_experts_kernel"),
        (CALL, "", sr.UNNAMED),
        ("fusion", L1 + "layer_1/self_attn/gqa_attn/dot_general", "gqa_attn"),
        ("fusion", L1 + "layer_2/feed_forward/moe_route/reshape", "moe_route"),
    ],
}
CASES = [(family, *row) for family, rows in TABLE.items() for row in rows]


@pytest.mark.parametrize("family,opcode,path,group", CASES)
def test_a_kernel_falls_to_the_group_of_its_scope(family, opcode, path, group):
    assert sr.group_of(sr.Groups(family).rules, opcode, path) == group


@pytest.mark.parametrize("family", sorted(TABLE))
def test_no_rule_goes_by_pallas_call_alone(family):
    """A kernel under a scope no rule knows is ``unnamed``, or its module's:
    never another kernel's group."""
    rules = sr.Groups(family).rules
    assert sr.group_of(rules, CALL, "jit(step)/pallas_call") == sr.UNNAMED
    assert sr.group_of(rules, CALL,
                       "jit(step)/new_scope/new_kernel/pallas_call") == sr.UNNAMED
    names = [g for g, _, _ in rules]
    assert names.index("attn_bwd") < names.index("attn_fwd")
