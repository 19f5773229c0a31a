"""Device time per step of the stream mixes (scope ``hc_mix``: the product
with ``phi``, the maps and their Sinkhorn rounds, the weighted read of the
residual streams and their mixed write; two a layer), forward, recomputed and
backward. Median over the traced steps, chip 0. Source: device trace, groups
``hc_mix_fwd`` and ``hc_mix_bwd`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("hc_mix_fwd", "hc_mix_bwd")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
