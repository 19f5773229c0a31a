"""Device time per step of the expert layer's per-token gather-sum: the
summed durations of the custom calls named ``moe_combine.<n>``
(``ops/moe_combine.py``: a token's held pairs' rows read where the sort put
them, weighted and summed, once forward for the layer's result and once
backward for the tokens' gradient). Median over the traced steps, chip 0. A
time, not a share of a roofline.

Why by name: the kernel runs under the scope ``moe_route``, but the first rule
of ``scope_groups/<family>.json`` that a ``pallas_call`` meets is
``attn_fwd``, so the GROUP ``attn_fwd`` holds it beside the flash kernels and
``moe_route_ms.train`` only what lies round it (the router, the sort, the row
gathers, the slots' index arithmetic). This reader goes by event name and
opcode, as ``gdn_kernel_ms.train`` reads the delta rule's kernels; routing's
time is ``moe_route_ms.train`` plus this. A program that sums the rows by a
scatter-add has no such event, and the metric is left out.

Also prints the line ``moe_combine_kernel: {...}`` with the kernel's
milliseconds and calls per step."""

import json
import re

from benchmark import scope_reduce
from benchmark import trace_reduce as tr

KERNEL = re.compile(r"^moe_combine(\.\d+)?$")
OPCODE = "custom-call"


def kernel_ns(trace, opcodes, match):
    """``(median ns per step, calls per step)`` of the kernel on chip 0 of a
    plain or scoped trace; ``None`` where no launch holds one."""
    planes = tr.device_planes(trace)
    if not planes:
        return None
    per_step = []
    for launch in tr.per_launch(planes[0], match):
        mine = [e[2] for e in launch["ops"]
                if KERNEL.search(e[0]) and opcodes.get(e[0]) == OPCODE]
        if mine:
            per_step.append(mine)
    if not per_step:
        return None
    return (tr.median([sum(s) for s in per_step]),
            tr.median([len(s) for s in per_step]))


def compute(run):
    if not run.trace:
        return None
    try:
        path = tr.find_xplane(run.trace_dir)
    except FileNotFoundError:
        return None
    _, opcodes = scope_reduce.op_metadata(path)
    found = kernel_ns(run.device_trace, opcodes, run.launch_match())
    if found is None:
        return None
    ns, calls = found
    print("moe_combine_kernel: " + json.dumps(
        {"ms": ns / 1e6, "calls_per_step": calls}), flush=True)
    return ns / 1e6
