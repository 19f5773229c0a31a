"""Share of a step's busy device time that no rule of
``scope_groups/<family>.json`` names: the instrument's own check, so that a
refactor that drops a scope shows. Median over the traced steps, chip 0.
Source: device trace.

Also prints the line ``scopes: {...}`` with every group's milliseconds per
step and, for ``hvd_exchange``, each distinct parent with its collective and
copy time."""

import json

from benchmark import scope_reduce


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None:
        return None
    print("scopes: " + json.dumps(result), flush=True)
    return 100.0 * result["groups_ms"][scope_reduce.UNNAMED] / result["busy_ms"]
