"""Device time per step of the Mamba-2 layers' own mechanism: the causal
convolution (scope ``ssm_conv``: taps, bias, ``silu``) and the selective scan
(``ssm_scan``: decays, the chunk-local products, the chunks' states, the scan
over chunks, ``D x``; no projection, no gated norm), forward, recomputed and
backward. Median over the traced steps, chip 0. Source: device trace, groups
``ssm_conv``, ``ssm_scan_fwd`` and ``ssm_scan_bwd`` of
``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("ssm_conv", "ssm_scan_fwd", "ssm_scan_bwd")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
