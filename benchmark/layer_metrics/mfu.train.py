"""Model FLOP/s utilization: the required operations of one step on one chip
(the family's count: recomputed operations do not count) over the median step
period in the trace (start of one launch to the start of the next, chip 0),
over the chip's published bf16 peak."""

from benchmark import manifest
from benchmark import trace_reduce as tr


def compute(run):
    planes = tr.device_planes(run.device_trace)
    if not planes:
        return None
    periods = [l["period"] for l in tr.per_launch(planes[0],
                                                  run.launch_match())
               if l["period"]]
    if not periods:
        return None
    peak = manifest.peak_for(run.devices[0].device_kind)["bf16_flops"]
    achieved = run.counters["ops_per_step"] / (tr.median(periods) / 1e9)
    return 100.0 * achieved / peak
