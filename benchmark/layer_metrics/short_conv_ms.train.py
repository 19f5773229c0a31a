"""Device time per step of the short-convolution mixers' own mechanism: the two
gates and the taps (scope ``short_conv``, no projection), forward, recomputed
and backward. Median over the traced steps, chip 0. Source: device trace,
groups ``short_conv_fwd`` and ``short_conv_bwd`` of
``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("short_conv_fwd", "short_conv_bwd")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
