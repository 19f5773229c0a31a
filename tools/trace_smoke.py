#!/usr/bin/env python
"""Fleet-tracing CI smoke (docs/timeline.md "Fleet tracing").

A 2-rank CPU job through the real elastic driver with HOROVOD_TRACE=1,
a seeded ``delay`` fault making rank 1 the straggler, and an injected
guard abort at the end — asserting the whole observability chain:

1. STEP SPANS + STRAGGLER ATTRIBUTION — each worker records 12 step
   spans through the ``wrap_step`` tap (a local compute phase, delayed
   on rank 1 for steps 4–9 by the fault plan, then a synchronizing
   allreduce); the driver's collection attributes the skew:
   ``hvd_step_skew_seconds`` observed and
   ``hvd_straggler_total{rank="1"}`` (never rank 0) on ``/metrics``.
2. MERGED FLEET TRACE — ``tools/trace_merge.py`` over the driver-
   collected windows loads as Chrome-trace JSON with one lane per rank,
   a driver lane carrying the generation publish, and per-lane
   clock-offset metadata (estimated over the KV ``/clock`` ping).
3. FLIGHT RECORDER — both ranks submit a NaN under
   ``HOROVOD_GUARD_NONFINITE=abort``; the abort path dumps each rank's
   ring, the driver bundles the dumps, and
   ``trace_merge.py --postmortem`` renders the aligned last-moments
   view with a ``DEATH:guard-abort`` marker per rank.
4. DETERMINISM — the run executes TWICE and a normalized summary of
   the artifacts (lane structure, step counts, straggler attribution,
   delay-event count, death reasons) must be byte-identical.

Exit 0 = all assertions hold. Wired as tools/ci_checks.sh stage 9
(skip: HVD_CI_SKIP_TRACE=1) and ``make trace-smoke``. Budget: ~2x15s.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

STEPS = 12
DELAY_S = 0.2
THRESHOLD_S = 0.05

FAULT_PLAN = {
    "seed": 4242,
    "faults": [
        {"kind": "delay", "rank": 1, "site": "step",
         "seconds": DELAY_S, "after": 3, "count": 6},
    ],
}

WORKER = f"""
    import os, time
    import numpy as np
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import horovod_tpu as hvd
    from horovod_tpu import trace as hvd_trace
    from horovod_tpu.fault import injector as fault_injector

    hvd.init()
    assert hvd.size() == 2
    assert hvd_trace.ACTIVE and hvd_trace.TAP is not hvd_trace.NULL_TAP

    def train_step(i):
        # Local compute phase — the straggler surface. The seeded plan
        # delays rank 1 here for steps 4-9.
        fault_injector.step(f'trace.step.{{i}}')
        time.sleep(0.02)

    step = hvd_trace.wrap_step(train_step, wire_dtype='f32', op='SUM')
    for i in range({STEPS}):
        step(i)
        # Synchronizing collective OUTSIDE the span: each step's skew is
        # the delay, not an accumulating drift.
        out = np.asarray(hvd.allreduce(
            np.ones(1024, np.float32), name=f'trace.grad.{{i}}',
            op=hvd.Sum))
        assert out[0] == hvd.size()
    # Window for the driver to collect + the smoke to scrape /metrics.
    time.sleep(4.0)
    # Injected abort -> flight-recorder dump via the guard path.
    bad = np.ones(64, np.float32)
    bad[3] = np.nan
    try:
        hvd.allreduce(bad, name='trace.poison')
        raise SystemExit('guard abort did not fire')
    except hvd.HorovodInternalError:
        pass
    print('TRACE_WORKER_DONE', hvd.rank(), flush=True)
    hvd.shutdown()
"""


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _scrape(port: int):
    from horovod_tpu.metrics import export as mexport

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ) as resp:
        return mexport.parse_prometheus(resp.read().decode())


def _straggler_counts(parsed) -> dict:
    fam = parsed.get("hvd_straggler_total", {"samples": []})
    return {
        labels.get("rank"): v
        for _, labels, v in fam["samples"]
        if v > 0 and labels.get("rank") is not None
    }


def _run_once(tag: str) -> str:
    """One full smoke pass; returns the normalized summary JSON."""
    port = _free_port()
    env = dict(os.environ)
    td = tempfile.mkdtemp(prefix=f"trace_smoke_{tag}_")
    trace_dir = os.path.join(td, "trace")
    env.update({
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "1",
        "HOROVOD_METRICS": "1",
        "HOROVOD_METRICS_PORT": str(port),
        "HOROVOD_METRICS_PUSH_INTERVAL_S": "0.25",
        "HOROVOD_TRACE": "1",
        "HOROVOD_TRACE_DIR": trace_dir,
        "HOROVOD_TRACE_PUSH_INTERVAL_S": "0.25",
        "HOROVOD_TRACE_STRAGGLER_THRESHOLD_S": str(THRESHOLD_S),
        "HOROVOD_GUARD_NONFINITE": "abort",
        "HOROVOD_FAULT_PLAN": json.dumps(FAULT_PLAN),
        "PYTHONPATH": os.pathsep.join(
            [REPO, env.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep),
    })
    script = os.path.join(td, "worker.py")
    with open(script, "w") as f:
        f.write(textwrap.dedent(WORKER))
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run",
         "-np", "2", "--min-np", "2", "--max-np", "2",
         "--output-dir", td, sys.executable, script],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    skew_seen = False
    stragglers: dict = {}
    deadline = time.monotonic() + 90
    try:
        while time.monotonic() < deadline and proc.poll() is None:
            time.sleep(0.25)
            try:
                parsed = _scrape(port)
            except Exception:  # noqa: BLE001 - driver not up yet
                continue
            skew = parsed.get("hvd_step_skew_seconds")
            if skew and any(
                name.endswith("_count") and v > 0
                for name, _, v in skew["samples"]
            ):
                skew_seen = True
            got = _straggler_counts(parsed)
            if got:
                stragglers = got
        out, _ = proc.communicate(
            timeout=max(5.0, deadline - time.monotonic())
        )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    text = out.decode(errors="replace")
    for fn in sorted(os.listdir(td)):
        if fn.startswith("worker.") and fn.endswith((".out", ".err")):
            with open(os.path.join(td, fn), errors="replace") as f:
                text += f"\n--- {fn} ---\n" + f.read()
    assert proc.returncode == 0, f"job failed rc={proc.returncode}\n{text}"
    assert "TRACE_WORKER_DONE 0" in text and "TRACE_WORKER_DONE 1" in text, text
    assert skew_seen, f"hvd_step_skew_seconds never observed\n{text}"
    assert "1" in stragglers, (
        f"straggler counter never named rank 1 (saw {stragglers})\n{text}"
    )
    assert "0" not in stragglers, (
        f"rank 0 charged as straggler: {stragglers}\n{text}"
    )

    # --- merged fleet trace ---
    from horovod_tpu.trace import merge as tmerge

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_merge as trace_merge_cli
    finally:
        sys.path.pop(0)

    assert trace_merge_cli.main([trace_dir]) == 0
    merged = os.path.join(trace_dir, "merged_trace.json")
    with open(merged) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    lanes = sorted({
        e["args"]["name"] for e in events
        if e.get("name") == "process_name"
    })
    assert lanes == ["driver", "rank 0", "rank 1"], lanes
    driver_names = {
        e["name"] for e in events if e.get("pid") == tmerge.DRIVER_PID
    }
    assert "hvd_generation_publish" in driver_names, driver_names
    assert "hvd_straggler" in driver_names, driver_names
    clock_estimated = {}
    for e in events:
        if e.get("name") == "hvd_clock_offset" and e["pid"] in (0, 1):
            clock_estimated[str(e["pid"])] = bool(
                e["args"].get("estimated")
            )
    ranks, _driver = tmerge.read_dir(trace_dir)
    steps_per_rank = {
        str(r): len(ranks[r].get("steps") or []) for r in sorted(ranks)
    }
    delay_events = sum(
        1 for line in ranks[1].get("event_log") or []
        if line.get("action") == "delay"
    )

    # --- postmortem ---
    assert trace_merge_cli.main([trace_dir, "--postmortem"]) == 0
    with open(os.path.join(trace_dir, "postmortem_trace.json")) as f:
        pm = json.load(f)
    deaths = pm["otherData"]["postmortem"]["reasons"]
    assert any(
        e["name"].startswith("DEATH:") for e in pm["traceEvents"]
    ), "no death markers in the postmortem render"
    bundle = os.path.join(trace_dir, "postmortem.json")
    assert os.path.exists(bundle), (
        "driver did not bundle the flight dumps"
    )

    return json.dumps({
        "schema": 1,
        "lanes": lanes,
        "steps_per_rank": steps_per_rank,
        "clock_estimated": clock_estimated,
        "driver_events": sorted(
            driver_names
            & {"hvd_driver_start", "hvd_generation_publish",
               "hvd_straggler"}
        ),
        "straggler_ranks": sorted(stragglers),
        "delay_events_rank1": delay_events,
        "deaths": {r: deaths[r] for r in sorted(deaths)},
    }, sort_keys=True)


def main() -> int:
    t0 = time.time()
    log1 = _run_once("a")
    log2 = _run_once("b")
    assert log1 == log2, (
        "trace smoke is not byte-stable across runs:\n"
        f"run1: {log1}\nrun2: {log2}"
    )
    doc = json.loads(log1)
    print(
        f"[trace-smoke] OK in {time.time() - t0:.1f}s: "
        f"{len(doc['lanes'])} lanes, "
        f"steps {doc['steps_per_rank']}, straggler rank "
        f"{doc['straggler_ranks']}, {doc['delay_events_rank1']} seeded "
        f"delays, deaths {doc['deaths']}, summary byte-stable"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
