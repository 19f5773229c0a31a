#!/usr/bin/env python
"""Per-phase latency breakdown of one eager allreduce: "profile
the split between linger, TCP negotiation RTT, and dispatch").

Run under the launcher:

    hvdrun -np 2 python tools/eager_latency_breakdown.py

Rank 0 prints one JSON line of median microseconds over the reps:

 - ``enq_to_plan``  — enqueue() return -> plan received by the consumer
   (C++ wake + solo-seal grace + TCP negotiation RTT + dispatch);
 - ``plan_to_exec`` — plan decode / entry matching in Python;
 - ``exec``         — the XLA data plane (compiled collective incl.
   peer-arrival skew);
 - ``done_to_ret``  — completion bookkeeping until synchronize returns;
 - ``ready_wait``   — any residual block_until_ready (async dispatch).

Round-5 numbers on the CI host (1 KB, 2 ranks, cycle 1 ms): the
caller-inline consumer (core/native_runtime.py synchronize) cut
enq_to_plan ~755 -> ~595 us and exec ~2084 -> ~1490 us (the executor
-thread wake hop and the cross-rank skew it caused), total ~2.9 ->
~2.2 ms.
"""
import json
import time


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    import jax.numpy as jnp

    rt = hvd._rt()
    rank = hvd.rank()
    x = jnp.asarray(np.random.randn(256).astype(np.float32))

    marks = {}
    orig_exec = rt._execute_plan

    def exec_wrap(plan):
        marks["plan_recv"] = time.perf_counter()
        r = orig_exec(plan)
        marks["exec_done"] = time.perf_counter()
        return r

    rt._execute_plan = exec_wrap
    orig_execute = rt.executor.execute

    def executor_wrap(plan, entries, topo):
        marks["exec_start"] = time.perf_counter()
        return orig_execute(plan, entries, topo)

    rt.executor.execute = executor_wrap

    jax.block_until_ready(hvd.allreduce(x, name="w"))
    rows = []
    for _ in range(80):
        time.sleep(0.002)
        marks.clear()
        t0 = time.perf_counter()
        out = hvd.allreduce(x, name="w")
        t_sync = time.perf_counter()
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        if all(k in marks for k in ("plan_recv", "exec_start", "exec_done")):
            rows.append({
                "enq_to_plan": (marks["plan_recv"] - t0) * 1e6,
                "plan_to_exec": (marks["exec_start"] - marks["plan_recv"])
                * 1e6,
                "exec": (marks["exec_done"] - marks["exec_start"]) * 1e6,
                "done_to_ret": (t_sync - marks["exec_done"]) * 1e6,
                "ready_wait": (t1 - t_sync) * 1e6,
                "total": (t1 - t0) * 1e6,
            })
    if rank == 0 and rows:
        med = lambda k: sorted(r[k] for r in rows)[len(rows) // 2]  # noqa: E731
        print("BREAKDOWN",
              json.dumps({k: round(med(k), 1) for k in rows[0]}),
              flush=True)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
