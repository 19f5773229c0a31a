"""The objective kernel's share of its roofline: the least time the chip could
take for the indexer's objective WITH its gradient (the larger of operations
over peak FLOP/s and bytes over peak bytes/s, from the family's
``sparse_index_kl_cost`` at the cell's shapes: over the SELECTED pairs the
heads' QK^T for the target, the indexer's scores and their two transposed
products) over the summed device time per step of the kernel's calls (the
trace names them ``sparse_index_kl.<n>``, after ``ops/sparse_index.py``'s
``pallas_call``): the first forward pass's call, which gives the value alone,
and the call in the backward, which gives the value and the gradient in one
walk. The first is overhead against the bound, as recomputed scores are
against ``attn_bwd_roofline``'s; a walk over every causal block pair does 4.3
times the selected pairs' work at 16384 positions. Median over the traced
steps, chip 0. A program that takes the objective by plain XLA has no such
event, and the metric is left out.

Also prints the line ``sparse_index_kl_kernel: {...}``."""

import json
import re

from benchmark import manifest
from benchmark import trace_reduce as tr

KERNEL = re.compile(r"^sparse_index_kl(\.\d+)?$")


def bound(run):
    """``(least_seconds, which)`` for one step's objective and gradient."""
    peak = manifest.peak_for(run.devices[0].device_kind)
    ops, nbytes = run.cell.family.sparse_index_kl_cost(
        run.cell.config, run.cell.traffic, run.counters["per_chip_batch"]
    )
    by_ops = ops / peak["bf16_flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes
                                   else "memory")


def compute(run):
    if not (run.trace and hasattr(run.cell.family, "sparse_index_kl_cost")):
        return None
    found = tr.named_ops_ns(run.device_trace, run.launch_match(),
                            KERNEL.search)
    if found is None:
        return None
    ns, calls = found
    least, which = bound(run)
    print("sparse_index_kl_kernel: " + json.dumps({
        "kernel_ms": ns / 1e6, "calls_per_step": calls,
        "least_ms": least * 1e3, "bound": which,
    }), flush=True)
    return 100.0 * least / (ns / 1e9)
