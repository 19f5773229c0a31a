"""Device time per step of the selective scan alone (scope ``ssm_scan``, what
``ops/ssd.py`` computes: no convolution, no projection, no gated norm),
forward, recomputed and backward. Median over the traced steps, chip 0.
Source: device trace, groups ``ssm_scan_fwd`` and ``ssm_scan_bwd`` of
``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("ssm_scan_fwd", "ssm_scan_bwd")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
