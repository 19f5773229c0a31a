"""Traffic kind ``train_steps``: the user's training loop.

One ``step(params, state, batch)`` call per batch from ``hvd.make_train_step``,
a fresh batch each step, the loss fetched to the host every ``fetch_every``-th
step (a logging cadence), the clock stopped on ``block_until_ready`` of the
last step. Yields ``train_samples_per_s_per_chip``.

Set-up builds ONE compiled step with its state, drives it from the seed
through its first ``check_steps`` steps by the window's own call and feed, and
hands that same object to the window. Those first steps are what `correct`
compares with the plain reference (``check_train.py``), which runs after the
window, once the program's state is freed.
"""

from __future__ import annotations

import time

from .. import check_train
from ..weights import make_params

END_TO_END = ("train_samples_per_s_per_chip",)
LAUNCH = r"^jit_step\("  # the step's launches on the trace's modules line


class Loop:
    """The window's own call and feed."""

    def __init__(self, step, params, state, feed, spans):
        self.step, self.params, self.state = step, params, state
        self.feed, self.spans = feed, spans
        self.loss = None
        self.calls = 0

    def call(self):
        batch = self.feed(self.calls)
        with self.spans.span("dispatch"):
            self.params, self.state, self.loss = self.step(
                self.params, self.state, batch
            )
        self.calls += 1
        return self.loss


def _program_numbers(cell, loop, fresh):
    """Drive the first steps and read the numbers `correct` compares."""
    import numpy as np

    cfg, n = cell.config, cell.traffic["check_steps"]
    losses, first = [], None
    for i in range(n):
        losses.append(float(loop.call()))
        if i == 0:
            first = np.asarray(check_train.leaf_norms(
                cell.family.first_gradient(cfg, loop.state)
            ))
    update = np.asarray(check_train.diff_norms(loop.params, fresh()))
    return {"losses": losses, "first_grad_norms": first,
            "update_norms": update}


def reference_numbers(cell, batches, seed, device, precision="highest"):
    """The plain reference through the same first steps, on one chip."""
    from jax.sharding import SingleDeviceSharding

    cfg, traffic = cell.config, cell.traffic
    ref = cell.reference()
    spec = cell.family.param_spec(cfg)
    one = SingleDeviceSharding(device)
    return check_train.reference_steps(
        lambda p, b, pr: ref.loss(p, b, cfg, pr),
        lambda: make_params(spec, seed, one),
        batches[: traffic["check_steps"]],
        ref.block_rows(cfg, traffic["per_chip_batch"]),
        cfg["train"]["optimizer"], precision,
    )


def build(ctx, batches):
    """The compiled step with its state and feed: one object for the check
    and the window."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu.jax as hvd

    cell, traffic = ctx.cell, ctx.cell.traffic
    axes = cell.options.get("mesh", {"data": cell.chips})
    mesh = hvd.build_mesh(axes, devices=ctx.devices)
    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P("data"))
    spec = cell.family.param_spec(cell.config)
    fresh = lambda: make_params(spec, ctx.seed, rep)
    step, tx = cell.family.build_train(
        cell.config, traffic, cell.options.get("step_options", {}), mesh
    )
    params = fresh()
    state = jax.jit(tx.init, out_shardings=rep)(params)
    # a fresh batch each step, put on the device from a pool on the host
    feed = lambda i: jax.device_put(batches[i % len(batches)], dat)
    return Loop(step, params, state, feed, ctx.spans), fresh


def window(ctx, loop, seconds):
    """The measured window. Returns ``(steps, elapsed_s, losses)``."""
    import jax

    every = ctx.cell.traffic.get("fetch_every", 10)
    losses = []
    first = loop.calls
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        losses.append(loop.call())
        if (loop.calls - first) % every == 0:
            with ctx.spans.span("fetch_loss"):
                float(losses[-1])
    with ctx.spans.span("wait_last"):
        jax.block_until_ready(loop.loss)
    elapsed = time.perf_counter() - t0
    return loop.calls - first, elapsed, losses


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    cell, traffic = ctx.cell, ctx.cell.traffic
    global_batch = traffic["per_chip_batch"] * cell.chips
    batches = cell.family.make_batches(
        cell.config, traffic, global_batch, ctx.seed, traffic["pool_batches"]
    )
    loop, fresh = build(ctx, batches)
    program = _program_numbers(cell, loop, fresh)
    for _ in range(traffic.get("warm_steps", 2)):
        loop.call()
    jax.block_until_ready(loop.loss)

    seconds = ctx.seconds
    if ctx.trace:
        seconds = min(seconds, traffic.get("trace_seconds", 4))
    ctx.start_window()
    steps, elapsed, losses = window(ctx, loop, seconds)
    ctx.end_window()
    values = np.asarray(jnp.stack(losses))
    bad = int(np.sum(~np.isfinite(values)))

    # The reference runs once the program's state is freed, so that the
    # memory peak is the program's and its seconds are not set-up.
    ctx.note("program_peak_bytes", ctx.memory_peak())
    del loop, losses
    t = time.perf_counter()
    reference = reference_numbers(cell, batches, ctx.seed, ctx.devices[0])
    ctx.reference_s = time.perf_counter() - t
    numbers = check_train.compare(program, reference)
    numbers["nonfinite_losses"] = float(bad)
    # other statistics of the same per-leaf norms, printed and not compared:
    # what a later limit would be set from (PERF.md section 7)
    ctx.note("leaf_gap_statistics", check_train.candidates(program, reference))

    rate = steps * global_batch / elapsed / cell.chips
    ops = cell.family.train_ops_per_step(cell.config, traffic,
                                         traffic["per_chip_batch"])
    ctx.note("steps", steps)
    ctx.note("window_s", elapsed)
    ctx.note("step_s", elapsed / steps)
    ctx.note("tokens_or_images_per_s_per_chip", rate
             * traffic.get("seq_len", 1))
    if ctx.devices[0].platform == "tpu":
        from ..manifest import peak_for

        peak = peak_for(ctx.devices[0].device_kind)["bf16_flops"]
        ctx.note("mfu_from_host_clock_percent",
                 100.0 * ops * steps / elapsed / peak)
    ctx.note("losses_first_check", program["losses"])
    ctx.note("reference_losses", reference["losses"])
    ctx.note("required_ops_per_step_per_chip", ops)
    ctx.counters.update(
        steps=steps, window_s=elapsed, global_batch=global_batch,
        per_chip_batch=traffic["per_chip_batch"], ops_per_step=ops,
        launch_pattern=LAUNCH,
    )
    return {
        "attempted": steps, "failed": bad, "numbers": numbers,
        "values": {"train_samples_per_s_per_chip": rate},
    }
