"""Device time per step of the attention backward: the ``flash_bwd`` scope of
``ops/pallas_attention.py`` (the scan's ``while``, its body and the ops round
it), each instant charged to the innermost operation running. Median over the
traced steps, chip 0. Source: device trace, group ``attn_bwd`` of
``scope_groups/<family>.json``."""

from benchmark import scope_reduce


def compute(run):
    return scope_reduce.group_ms(run, "attn_bwd")
