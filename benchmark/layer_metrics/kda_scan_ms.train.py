"""Device time per step of the per-channel delta rule alone (scope
``kda_scan``, what ``ops/kda.py`` computes and the gate in front of it: no
convolution, no projection, no gated norm), forward, recomputed and backward.
Median over the traced steps, chip 0. Source: device trace, groups
``kda_scan_fwd`` and ``kda_scan_bwd`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("kda_scan_fwd", "kda_scan_bwd")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
