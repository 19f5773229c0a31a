"""The forward per-channel delta rule's share of its roofline (all
Kimi-delta-attention layers).

The least time the chip could take (the larger of operations over peak FLOP/s
and bytes over peak bytes/s, from the family's ``kda_fwd_cost`` at the cell's
shapes: the per-token recurrence's operations, whatever implements it, and
``q``, ``k``, ``v``, the gate and ``beta`` read and ``o`` written once) over
the device time per step of the group ``kda_scan_fwd`` of
``scope_groups/<family>.json``: the first forward pass alone (recomputed and
transposed operations are not the forward's). Median over the traced steps,
chip 0."""

from benchmark import manifest, scope_reduce


def bound(run):
    """``(least_seconds, which)`` for one step's forward."""
    peak = manifest.peak_for(run.devices[0].device_kind)
    ops, nbytes = run.cell.family.kda_fwd_cost(
        run.cell.config, run.cell.traffic, run.counters["per_chip_batch"]
    )
    by_ops = ops / peak["bf16_flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes
                                   else "memory")


def compute(run):
    if not hasattr(run.cell.family, "kda_fwd_cost"):
        return None
    result = scope_reduce.of_run(run)
    if result is None or not result["groups_ms"].get("kda_scan_fwd"):
        return None
    least, _ = bound(run)
    return 100.0 * least / (result["groups_ms"]["kda_scan_fwd"] / 1e3)
