"""The selection kernel's share of its roofline: the least time the chip
could take for ONE forward pass's selections (the larger of operations over
peak FLOP/s and bytes over peak bytes/s, from the family's
``sparse_index_select_cost`` at the cell's shapes: the indexer heads' products
over the CAUSAL pairs; the rectified weighted sum, the search for each row's
k-th value and the mask are in the time and not in the bound) over the device
time of one pass's calls of the kernel (the trace names them
``sparse_index_select.<n>``, after ``ops/sparse_index.py``'s
``pallas_call``). Chip 0.

One pass's calls: the family says how many calls a pass makes
(``sparse_index_calls``: one a layer); a step with per-layer recomputation on
runs the pass twice, and one pass's time is that share of the events' sum, as
``attn_fwd_roofline`` holds the forward flash kernel. A program that selects
by plain XLA has no such event, and the metric is left out.

Also prints the line ``sparse_index_select_kernel: {...}``."""

import json
import re

from benchmark import manifest
from benchmark import trace_reduce as tr

KERNEL = re.compile(r"^sparse_index_select(\.\d+)?$")


def bound(run):
    """``(least_seconds, which)`` for one forward pass's kernel calls."""
    peak = manifest.peak_for(run.devices[0].device_kind)
    ops, nbytes = run.cell.family.sparse_index_select_cost(
        run.cell.config, run.cell.traffic, run.counters["per_chip_batch"]
    )
    by_ops = ops / peak["bf16_flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes
                                   else "memory")


def compute(run):
    family = run.cell.family
    if not (run.trace and hasattr(family, "sparse_index_select_cost")
            and hasattr(family, "sparse_index_calls")):
        return None
    found = tr.named_ops_ns(run.device_trace, run.launch_match(),
                            KERNEL.search)
    if found is None:
        return None
    ns, calls = found
    per_pass = family.sparse_index_calls(run.cell.config)
    least, which = bound(run)
    print("sparse_index_select_kernel: " + json.dumps({
        "kernel_ms": ns / 1e6, "calls_per_step": calls,
        "calls_per_pass": per_pass, "passes": calls / per_pass,
        "least_ms": least * 1e3, "bound": which,
    }), flush=True)
    # the time of as many calls as one pass makes
    return 100.0 * least / (ns * per_pass / calls / 1e9)
