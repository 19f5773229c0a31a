"""The forward flash kernel's share of its roofline: the least time the chip
could take for ONE forward pass over the attention layers (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from the family's
``attn_fwd_cost`` at the cell's shapes) over the device time of one pass's
calls of the kernel (the trace names them ``attention.<n>``, after the forward
``pallas_call`` of ``ops/pallas_attention.py``; the backward's kernels are
``flash_bwd.<n>`` and ``attn_bwd_roofline``'s). Chip 0.

One pass's calls: the family says how many calls a pass makes
(``attn_fwd_calls``: one an attention layer). A step with per-layer
recomputation on runs the pass twice, so its events are two passes' and one
pass's time is half their sum; held against the sum, as this reader held it
until PR 40, one pass's bound could not pass 50% whatever the kernel did.
Recomputed operations are still no required work: ``mfu.train`` does not count
them.

Also prints the line ``attn_fwd_kernel: {...}`` with the kernel's milliseconds
and calls per step, the calls a pass makes and the passes that come to."""

import json
import re

from benchmark import manifest
from benchmark import trace_reduce as tr

KERNEL = re.compile(r"^attention(\.\d+)?$")  # the forward kernel's name in the trace


def bound(run):
    """``(least_seconds, which)`` for one forward pass's kernel calls."""
    peak = manifest.peak_for(run.devices[0].device_kind)
    ops, nbytes = run.cell.family.attn_fwd_cost(
        run.cell.config, run.cell.traffic, run.counters["per_chip_batch"]
    )
    by_ops = ops / peak["bf16_flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes
                                   else "memory")


def kernel_ns(trace, match):
    """``(median ns per step, calls per step)`` of the forward kernel on chip
    0; ``None`` where no launch holds one."""
    return tr.named_ops_ns(trace, match, KERNEL.search)


def compute(run):
    family = run.cell.family
    if not (hasattr(family, "attn_fwd_cost")
            and hasattr(family, "attn_fwd_calls")):
        return None
    found = kernel_ns(run.device_trace, run.launch_match())
    if found is None:
        return None
    ns, calls = found
    per_pass = family.attn_fwd_calls(run.cell.config)
    least, which = bound(run)
    print("attn_fwd_kernel: " + json.dumps({
        "kernel_ms": ns / 1e6, "calls_per_step": calls,
        "calls_per_pass": per_pass, "passes": calls / per_pass,
        "least_ms": least * 1e3, "bound": which,
    }), flush=True)
    # the time of as many calls as one pass makes
    return 100.0 * least / (ns * per_pass / calls / 1e9)
