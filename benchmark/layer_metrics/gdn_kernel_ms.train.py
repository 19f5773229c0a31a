"""Device time per step of the delta rule's chunk-local Pallas kernels: the
summed durations of the custom calls named ``gdn_fwd.<n>`` and ``gdn_bwd.<n>``
(``ops/gated_delta.py``: what a chunk needs before the scan over chunks, and
its backward), forward, recomputed and backward. Median over the traced
steps, chip 0. A time, not a share of a roofline.

The kernels run under the scope ``gdn_scan``, so the groups ``gdn_scan_fwd``
and ``gdn_scan_bwd`` of ``scope_groups/qwen3_next.json`` hold them beside the
scan over chunks and the copies, and ``gdn_ms.train`` is the whole delta rule
(from PR 31 to PR 39 a rule on every ``pallas_call`` sent them to ``attn_fwd``,
and the layer's time was that metric plus this one). This reader goes by event
name and opcode, as ``attn_bwd_roofline`` reads ``flash_bwd.<n>``, and is the
kernels' OWN part of ``gdn_ms.train``: what a change to the kernels moves, and
what a change to the scan round them does not. A program whose chunk-local
part is plain XLA has no such event, and the metric is left out.

Also prints the line ``gdn_kernels: {...}`` with each direction's milliseconds
and calls per step."""

import json
import re

from benchmark import scope_reduce
from benchmark import trace_reduce as tr

KERNELS = {"fwd": re.compile(r"^gdn_fwd(\.\d+)?$"),
           "bwd": re.compile(r"^gdn_bwd(\.\d+)?$")}
OPCODE = "custom-call"


def kernel_ns(trace, opcodes, match):
    """``{direction: (median ns per step, calls per step)}`` of the kernels on
    chip 0 of a plain or scoped trace, with ``"all"`` for both together;
    ``None`` where no launch holds one."""
    planes = tr.device_planes(trace)
    if not planes:
        return None
    per_step = []
    for launch in tr.per_launch(planes[0], match):
        mine = {which: [e[2] for e in launch["ops"] if pattern.search(e[0])
                        and opcodes.get(e[0]) == OPCODE]
                for which, pattern in KERNELS.items()}
        if any(mine.values()):
            mine["all"] = [t for ts in mine.values() for t in ts]
            per_step.append(mine)
    if not per_step:
        return None
    return {which: (tr.median([sum(s[which]) for s in per_step]),
                    tr.median([len(s[which]) for s in per_step]))
            for which in ("fwd", "bwd", "all")}


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
    print("gdn_kernels: " + json.dumps({
        which + "_" + what: value
        for which, (ns, calls) in found.items()
        for what, value in (("ms", ns / 1e6), ("calls_per_step", calls))
    }), flush=True)
    return found["all"][0] / 1e6
