"""Device time per step of the attention backward: everything under the
``flash_bwd`` scope of ``ops/pallas_attention.py``, the Pallas kernels
(``flash_bwd.<n>`` in the trace, whose own share of their roofline is
``attn_bwd_roofline``) and what lies round them (``D = rowsum(do * o)``, the
statistics' reshapes, layout copies), each instant charged to the innermost
operation running. Median over the traced steps, chip 0. Source: device trace,
group ``attn_bwd`` of ``scope_groups/<family>.json``, whose rule comes before
``attn_fwd``'s (from PR 26 to PR 39 it came after, and this read the
remainder round the kernels alone)."""

from benchmark import scope_reduce


def compute(run):
    return scope_reduce.group_ms(run, "attn_bwd")
