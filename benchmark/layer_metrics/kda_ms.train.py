"""Device time per step of the Kimi-delta-attention mixers, all of them (scope
``kda_mixer``: the six projections and ``b_proj``, the three convolutions,
the gate, the per-channel delta rule, the gated per-head norm and ``o_proj``),
forward, recomputed and backward. Median over the traced steps, chip 0.
Source: device trace, groups ``kda_conv``, ``kda_scan_fwd``, ``kda_scan_bwd``
and ``kda_mixer`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("kda_conv", "kda_scan_fwd", "kda_scan_bwd", "kda_mixer")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
