"""Eager-vs-compiled allreduce micro-benchmark worker.

Runs under the launcher (``hvdrun -np 2``) on the CPU backend and measures,
at a real communicator size, three latencies per payload size:

 - ``eager_np_us``   — numpy input through the full eager pipeline
   (enqueue → native-core negotiation → compiled XLA psum → host copy out);
 - ``eager_dev_us``  — jax-array input through the same pipeline's
   device-resident fast path (no ``device_put``/``np.asarray``; pack +
   collective + unpack are one executable, outputs stay on device);
 - ``compiled_us``   — the data-plane floor: the executor's OWN device
   path (identical global-array construction + the SAME cached
   executable an eager call uses) invoked directly, without the control
   plane. ``eager - compiled`` therefore isolates exactly the control
   plane (enqueue, negotiation, plan dispatch, thread handoffs) by
   construction. An independently-built ``shard_map(psum)`` is also
   timed (``ref_psum_*`` columns) for cross-checking, but it is a
   DIFFERENT collective program — at bandwidth-bound sizes its time can
   exceed the eager path's, which is why basing overhead on it produced
   negative rows.

``eager_* - compiled`` is the per-call overhead of the eager control plane —
the number the reference pays between framework op and NCCL launch.
Rank 0 prints one JSON line ``{"rows": [...]}``.

This is a CPU tool by design: multi-rank needs one device per process, and
the benchmark's subject (host-side pipeline overhead) is
platform-independent.
"""

from __future__ import annotations

import json
import sys
import time


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.jax import _shard_map

    size, rank = hvd.size(), hvd.rank()
    assert size > 1, "micro_bench must run under the launcher (-np >= 2)"

    # Same mesh the eager executor builds: one (leading) device per process.
    from horovod_tpu.core.xla_executor import rank_mesh_devices

    mesh_devices = rank_mesh_devices()
    mesh = Mesh(np.array(mesh_devices), ("micro",))
    sharding = NamedSharding(mesh, P("micro"))
    local_device = mesh_devices[rank]
    # Floor layout matches the eager executor's zero-copy device path
    # exactly (dim0-sharded global, local array = its own shard): the
    # floor must lower-bound the pipeline, not measure a different
    # (leading-axis) layout with its own copy behavior.
    psum_fn = jax.jit(
        _shard_map(
            lambda x: lax.psum(x, "micro"), mesh,
            in_specs=(P("micro"),), out_specs=P(),
        )
    )

    def global_arr(x_np):
        local = jax.device_put(x_np, local_device)
        return jax.make_array_from_single_device_arrays(
            (size * x_np.shape[0],) + x_np.shape[1:], sharding, [local]
        )

    # Untimed alignment barrier before every timed floor rep: the eager
    # pipeline's negotiation aligns the ranks right before its collective
    # launches, so an UNsynchronized floor loop measures peer-arrival
    # skew as latency and can exceed the full eager time at
    # bandwidth-bound sizes (negative overhead). A tiny
    # psum aligns ranks to within microseconds at negligible cost
    # (psum_fn specializes per shape; only the array is tiny).
    _bar = global_arr(np.zeros(1, np.float32))

    def align():
        jax.block_until_ready(psum_fn(_bar))

    rows = []
    for nbytes in (1 << 10, 1 << 16, 1 << 20, 1 << 24):
        n = nbytes // 4
        x_np = np.random.RandomState(rank).randn(n).astype(np.float32)
        x_dev = jnp.asarray(x_np)
        # Rep counts sized so the median is stable (3 reps
        # at 16 MB let harness noise exceed signal and produced negative
        # overhead rows): >=10 even for the largest payload, 100 for the
        # latency-dominated small ones.
        reps = max(10, min(100, (1 << 25) // nbytes))

        # Compiled floor: the executor's own data-plane path, no control
        # plane. Both ranks call it in lockstep (deterministic loop), so
        # the cross-rank collective stays ordered without negotiation.
        # The pure-Python Runtime fallback (native core unavailable /
        # HOROVOD_TPU_CORE=python) has no .executor — fall back to the
        # independent psum program as the floor there, flagged per row.
        from horovod_tpu.common.types import ReduceOp, TensorTableEntry

        rt_ex = getattr(hvd._rt(), "executor", None)
        if rt_ex is not None and hasattr(rt_ex, "_allreduce_device"):
            floor_source = "executor_device_path"

            def floor_call():
                e = TensorTableEntry(name=f"floor_{nbytes}", tensor=x_dev)
                return rt_ex._allreduce_device(
                    [e], op=ReduceOp.SUM, adasum=False, hier=False,
                    pre=1.0, post=1.0, participants=size,
                )[f"floor_{nbytes}"]
        else:
            floor_source = "independent_psum"
            _floor_garr = global_arr(x_np)

            def floor_call():
                return psum_fn(_floor_garr)

        jax.block_until_ready(floor_call())
        ts = []
        for _ in range(reps):
            align()
            t0 = time.perf_counter()
            jax.block_until_ready(floor_call())
            ts.append(time.perf_counter() - t0)
        t_comp, t_comp_med = sum(ts) / reps, sorted(ts)[reps // 2]
        # Noise band of the floor itself (IQR): at bandwidth-bound sizes
        # run-to-run variance of the collective exceeds the control
        # plane's contribution, and an overhead below the band is
        # indistinguishable from zero — report it as such instead of a
        # meaningless (sometimes negative) difference.
        srt = sorted(ts)
        noise_band = srt[(3 * reps) // 4] - srt[reps // 4]

        # Independent reference program (cross-check only; see module
        # docstring for why it must not be the overhead baseline).
        garr = global_arr(x_np)
        jax.block_until_ready(psum_fn(garr))
        ts = []
        for _ in range(reps):
            align()
            t0 = time.perf_counter()
            jax.block_until_ready(psum_fn(garr))
            ts.append(time.perf_counter() - t0)
        t_ref, t_ref_med = sum(ts) / reps, sorted(ts)[reps // 2]

        # Eager, numpy input (host pack + device_put + collective + asarray).
        # One name reused across reps — the training-steady-state pattern
        # (grad names repeat every step), which also exercises the core's
        # response-cache bit path like the reference's repeat iterations.
        hvd.allreduce(x_np, name=f"micro_np_{nbytes}")
        ts = []
        for i in range(reps):
            t0 = time.perf_counter()
            hvd.allreduce(x_np, name=f"micro_np_{nbytes}")
            ts.append(time.perf_counter() - t0)
        t_np, t_np_med = sum(ts) / reps, sorted(ts)[reps // 2]

        # Eager, device input (zero-host-copy fast path), same-name reuse.
        jax.block_until_ready(
            hvd.allreduce(x_dev, name=f"micro_dev_{nbytes}")
        )
        ts = []
        for i in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(
                hvd.allreduce(x_dev, name=f"micro_dev_{nbytes}")
            )
            ts.append(time.perf_counter() - t0)
        t_dev, t_dev_med = sum(ts) / reps, sorted(ts)[reps // 2]

        def _ovh(eager_med):
            d = eager_med - t_comp_med
            if abs(d) <= noise_band:
                return 0.0, True
            return round(d * 1e6, 1), False

        ovh_np, np_noise = _ovh(t_np_med)
        ovh_dev, dev_noise = _ovh(t_dev_med)
        rows.append({
            "bytes": nbytes,
            "np": size,
            "reps": reps,
            "noise_band_us": round(noise_band * 1e6, 1),
            "overhead_within_noise": {"np": np_noise, "dev": dev_noise},
            "floor_source": floor_source,
            # Medians FIRST-CLASS: robust to scheduler spikes (CI hosts
            # can be a single shared core; one 10ms preemption dominates
            # a mean). Quote these; the means are kept for reference.
            "eager_np_med_us": round(t_np_med * 1e6, 1),
            "eager_dev_med_us": round(t_dev_med * 1e6, 1),
            "compiled_med_us": round(t_comp_med * 1e6, 1),
            "overhead_np_med_us": ovh_np,
            "overhead_dev_med_us": ovh_dev,
            "eager_np_us": round(t_np * 1e6, 1),
            "eager_dev_us": round(t_dev * 1e6, 1),
            "compiled_us": round(t_comp * 1e6, 1),
            "overhead_np_us": round((t_np - t_comp) * 1e6, 1),
            "overhead_dev_us": round((t_dev - t_comp) * 1e6, 1),
            "ref_psum_med_us": round(t_ref_med * 1e6, 1),
            "ref_psum_us": round(t_ref * 1e6, 1),
        })
        # Keep ranks in lockstep between payload sizes.
        hvd.allreduce(np.zeros(1, np.float32), name=f"micro_bar_{nbytes}")

    if rank == 0:
        print(json.dumps({"rows": rows}), flush=True)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
