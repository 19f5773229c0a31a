"""Share of the traced steady window in which no operation ran on chip 0:
1 - busy / window. Source: device trace."""

from benchmark import trace_reduce as tr


def compute(run):
    return tr.idle_share_percent(run.device_trace, run.launch_match())
