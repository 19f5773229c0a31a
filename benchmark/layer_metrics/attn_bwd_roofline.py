"""The backward flash kernels' share of their roofline: the least time the
chip could take for one step's attention backward over the summed device time
per step of the kernels' events. Median over the traced steps, chip 0.

The events: custom calls named ``flash_bwd.<n>``, after the two backward
``pallas_call``s of ``ops/pallas_attention.py`` under the scope ``flash_bwd``
(one kernel a layer where a row's whole ``dq`` fits VMEM, a dK/dV and a dQ
kernel where not). The group ``attn_bwd`` of ``scope_groups/*.json``, which
``attn_bwd_ms.train`` reads, holds these kernels AND what lies round them;
this reader goes by event name and opcode and holds the kernels alone. A
program whose backward is no kernel (an XLA ``while``) has no such event, and
the metric is left out.

The least time: the larger of operations over peak FLOP/s and bytes over peak
bytes/s, both twice the family's ``attn_fwd_cost``: the four products the
mathematics needs beside the forward's two (dV, dP, dQ, dK, as ``mfu.train``
counts them; the scores the kernels recompute are overhead, so two kernels
that redo QK^T and dP cannot pass 4/7 = 57%), and q, k, v, o, dO read and dq,
dk, dv written once where the forward moves four such arrays.

Also prints the line ``attn_bwd_kernels: {...}`` with the kernels' milliseconds
per step: the kernels' own part of ``attn_bwd_ms.train``."""

import json
import re

from benchmark import manifest, scope_reduce
from benchmark import trace_reduce as tr

KERNEL = re.compile(r"^flash_bwd(\.\d+)?$")  # the backward kernels in the trace
OPCODE = "custom-call"


def kernel_ns(trace, opcodes, match):
    """``(median ns per step, calls per step)`` of the backward kernels on
    chip 0 of a plain or scoped trace; ``None`` where no launch holds one."""
    return tr.named_ops_ns(
        trace, match,
        lambda name: KERNEL.search(name) and opcodes.get(name) == OPCODE)


def bound(run):
    """``(least_seconds, which)`` for one step's backward kernel calls."""
    peak = manifest.peak_for(run.devices[0].device_kind)
    ops, nbytes = run.cell.family.attn_fwd_cost(
        run.cell.config, run.cell.traffic, run.counters["per_chip_batch"]
    )
    by_ops = 2.0 * ops / peak["bf16_flops"]
    by_bytes = 2.0 * nbytes / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes
                                   else "memory")


def compute(run):
    if not run.trace or not hasattr(run.cell.family, "attn_fwd_cost"):
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
    least, which = bound(run)
    print("attn_bwd_kernels: " + json.dumps({
        "kernels_ms": ns / 1e6, "calls_per_step": calls,
        "least_ms": least * 1e3, "bound": which,
    }), flush=True)
    return 100.0 * least / (ns / 1e9)
