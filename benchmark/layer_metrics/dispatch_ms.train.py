"""Host time of one ``step(...)`` call until it returns (the enqueue), median
over the window's calls. Source: the benchmark's span round the call."""

import statistics


def compute(run):
    durations = run.spans.durations("dispatch", since=run.window_start)
    if not durations:
        return None
    return statistics.median(durations) * 1000.0
