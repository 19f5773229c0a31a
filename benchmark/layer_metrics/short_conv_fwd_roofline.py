"""The forward short convolution's share of its roofline (all convolution
layers).

The least time the chip could take (the larger of operations over peak FLOP/s
and bytes over peak bytes/s, from the family's ``short_conv_fwd_cost`` at the
cell's shapes: B, C and u read and the gated result written once in bf16) over
the device time per step of the group ``short_conv_fwd`` of
``scope_groups/<family>.json``: the first forward pass alone (recomputed and
transposed operations are not the forward's). Median over the traced steps,
chip 0."""

from benchmark import manifest, scope_reduce


def bound(run):
    """``(least_seconds, which)`` for one step's forward."""
    peak = manifest.peak_for(run.devices[0].device_kind)
    ops, nbytes = run.cell.family.short_conv_fwd_cost(
        run.cell.config, run.cell.traffic, run.counters["per_chip_batch"]
    )
    by_ops = ops / peak["bf16_flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes
                                   else "memory")


def compute(run):
    if not hasattr(run.cell.family, "short_conv_fwd_cost"):
        return None
    result = scope_reduce.of_run(run)
    if result is None or not result["groups_ms"].get("short_conv_fwd"):
        return None
    least, _ = bound(run)
    return 100.0 * least / (result["groups_ms"]["short_conv_fwd"] / 1e3)
