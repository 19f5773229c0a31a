"""The forward grouped products' share of their roofline (the expert layers'
held experts over the expected pairs, all layers).

The least time the chip could take (the larger of operations over peak FLOP/s
and bytes over peak bytes/s, from the family's ``moe_experts_cost`` at the
cell's shapes) over the device time per step of the FIRST forward pass's
grouped products: the ``ragged-dot-...`` kernels that ran before the head's
transposed product, with which the backward begins (a kernel's custom call
carries its own name, not the program's scope, so forward calls are told from
recomputed and transposed ones by when they ran; the compiler moves small
transposed operations, casts and broadcasts, ahead of the forward, the head's
product it cannot), plus the group ``moe_experts_fwd`` of
``scope_groups/<family>.json``. Median over the traced steps, chip 0. No such
kernel in the trace: nothing to read."""

import re

from benchmark import manifest, scope_reduce
from benchmark import trace_reduce as tr

KERNEL = re.compile(r"^ragged-dot")   # the grouped products' name in the trace
# the scope path of the head's transposed products: the backward's first
BACKWARD = re.compile(r"transpose\(.*/lm_head/")


def bound(run):
    """``(least_seconds, which)`` for one step's forward."""
    peak = manifest.peak_for(run.devices[0].device_kind)
    ops, nbytes = run.cell.family.moe_experts_cost(
        run.cell.config, run.cell.traffic, run.counters["per_chip_batch"]
    )
    by_ops = ops / peak["bf16_flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes
                                   else "memory")


def forward_kernels_ns(ops, paths):
    """Of one launch's ``[name, start, duration]`` events: the summed time
    of the kernels that started before the backward did; ``None`` where the
    backward's beginning cannot be found."""
    backward = [start for name, start, _ in ops
                if BACKWARD.search(paths.get(name, ""))]
    if not backward:
        return None
    first = min(backward)
    return sum(dur for name, start, dur in ops
               if KERNEL.search(name) and start < first)


def forward_kernel_ms(run):
    """Summed time per step of the kernels of the first forward pass."""
    planes = tr.device_planes(run.device_trace)
    if not planes:
        return None
    paths, _ = scope_reduce.op_metadata(tr.find_xplane(run.trace_dir))
    per_step = [forward_kernels_ns(launch["ops"], paths)
                for launch in tr.per_launch(planes[0], run.launch_match())]
    if not per_step or None in per_step or not tr.median(per_step):
        return None
    return tr.median(per_step) / 1e6


def compute(run):
    if not hasattr(run.cell.family, "moe_experts_cost"):
        return None
    result = scope_reduce.of_run(run)
    if result is None or "moe_experts_fwd" not in result["groups_ms"]:
        return None
    kernels = forward_kernel_ms(run)
    if kernels is None:
        return None
    least, _ = bound(run)
    return 100.0 * least / ((kernels + result["groups_ms"]["moe_experts_fwd"])
                            / 1e3)
