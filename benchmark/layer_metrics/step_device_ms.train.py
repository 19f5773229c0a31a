"""Busy time of the device per step (union of op intervals inside one launch
of the step), median over the traced steps, chip 0. Source: device trace."""

from benchmark import trace_reduce as tr


def compute(run):
    return tr.launch_busy_ms(run.device_trace, run.launch_match())
