"""Device time per step of the Gated DeltaNet layers' own mechanism: the causal
convolution (scope ``gdn_conv``) and the chunked delta rule (``gdn_scan``, no
projection: the chunk-local Pallas kernels, whose own part is
``gdn_kernel_ms.train``, the scan over chunks and the copies), forward,
recomputed and backward. Median over the traced steps,
chip 0. Source: device trace, groups ``gdn_conv``, ``gdn_scan_fwd`` and
``gdn_scan_bwd`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("gdn_conv", "gdn_scan_fwd", "gdn_scan_bwd")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
