"""Device time per step of the expert layer's per-token gather-sum: the
summed durations of the custom calls named ``moe_combine.<n>``
(``ops/moe_combine.py``: a token's held pairs' rows read where the sort put
them, weighted and summed, once forward for the layer's result and once
backward for the tokens' gradient). Median over the traced steps, chip 0. A
time, not a share of a roofline.

The kernel runs under the scope ``moe_route``, so the group ``moe_route`` of
``scope_groups/<family>.json`` holds it beside the router, the sort, the row
gathers and the slots' index arithmetic, and ``moe_route_ms.train`` is the
whole of routing (from PR 37 to PR 39 a rule on every ``pallas_call`` sent it
to ``attn_fwd``, and routing's time was that metric plus this one). This
reader goes by event name and opcode, as ``gdn_kernel_ms.train`` reads the
delta rule's kernels, and is the kernel's OWN part of ``moe_route_ms.train``.
A program that sums the rows by a scatter-add has no such event, and the
metric is left out.

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
    return tr.named_ops_ns(
        trace, match,
        lambda name: KERNEL.search(name) and opcodes.get(name) == OPCODE)


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
