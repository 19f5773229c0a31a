"""The forward flash kernel's share of its roofline: the least time the chip
could take for one step's forward attention (the larger of operations over
peak FLOP/s and bytes over peak bytes/s, from the family's count at the cell's
shapes) over the summed device time per step of the kernel's events
(the trace names them ``attention.<n>``, after the forward ``pallas_call`` of
``ops/pallas_attention.py``; the backward is an XLA ``while`` and has no name
of its own). Chip 0."""

import re

from benchmark import manifest
from benchmark import trace_reduce as tr

KERNEL = re.compile(r"^attention(\.\d+)?$")  # the forward kernel's name in the trace


def bound(run):
    """``(least_seconds, which)`` for one step's forward kernel calls."""
    peak = manifest.peak_for(run.devices[0].device_kind)
    ops, nbytes = run.cell.family.attn_fwd_cost(
        run.cell.config, run.cell.traffic, run.counters["per_chip_batch"]
    )
    by_ops = ops / peak["bf16_flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes
                                   else "memory")


def compute(run):
    if not hasattr(run.cell.family, "attn_fwd_cost"):
        return None
    planes = tr.device_planes(run.device_trace)
    if not planes:
        return None
    per_step = []
    for launch in tr.per_launch(planes[0], run.launch_match()):
        t = sum(e[2] for e in launch["ops"] if KERNEL.search(e[0]))
        if t:
            per_step.append(t)
    if not per_step:
        return None
    least, _ = bound(run)
    return 100.0 * least / (tr.median(per_step) / 1e9)
