"""Summed durations per step of the collective operations (all-reduce,
reduce-scatter, all-gather, collective-permute), median over the traced steps,
chip 0. Source: device trace."""

from benchmark import trace_reduce as tr


def compute(run, exposed=False):
    planes = tr.device_planes(run.device_trace)
    if len(planes) < 2:
        return None
    launches = tr.per_launch(planes[0], run.launch_match())
    times = [tr.collective_times(l)[1 if exposed else 0] for l in launches]
    if not times:
        return None
    return tr.median(times) / 1e6
