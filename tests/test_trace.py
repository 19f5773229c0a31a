"""Fleet-tracing subsystem (horovod_tpu/trace): tap discipline and the
zero-overhead step tap, the flight recorder, clock-offset estimation and
KV shipping, driver-side skew attribution, the trace merge/postmortem
renderer, and the timeline satellites (writer-crash drop accounting,
shutdown-timeout detection, runtime-control contract) — docs/timeline.md
"Fleet tracing" is the prose companion."""

import json
import logging
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu import trace as hvd_trace
from horovod_tpu.trace import merge as tmerge
from horovod_tpu.trace import pusher as tpush
from horovod_tpu.utils.timeline import Timeline, TimelineWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Every test starts and ends with both taps in their env-default
    state (inactive in the test environment)."""
    hvd_trace.reset()
    hvd_trace.reset_build_ledger()
    hvd_metrics.reset()
    yield
    hvd_trace.reset()
    hvd_trace.reset_build_ledger()
    hvd_metrics.reset()


# ---------------------------------------------------------- tap discipline
def test_disabled_tap_is_shared_noop_singleton():
    assert not hvd_trace.ACTIVE
    assert hvd_trace.TAP is hvd_trace.NULL_TAP
    assert hvd_trace.tap() is hvd_trace.NULL_TAP
    # No-ops never record anything.
    hvd_trace.TAP.event("x", foo=1)
    hvd_trace.TAP.commit_step()
    hvd_trace.TAP.end_step(hvd_trace.TAP.begin_step())
    assert hvd_trace.TAP.window() == {}
    assert hvd_trace.flight_dump("nope") is None


def test_wrap_step_is_identity_when_disabled():
    """The zero-overhead proof: with tracing off, wrap_step returns the
    step function ITSELF — not a pass-through wrapper."""
    assert not hvd_trace.ACTIVE

    def step():
        return 7

    assert hvd_trace.wrap_step(step, wire_dtype="f32") is step


def test_install_and_reset_swap_the_singleton():
    hvd_trace.install(True)
    assert hvd_trace.ACTIVE
    assert hvd_trace.TAP is not hvd_trace.NULL_TAP
    hvd_trace.reset()
    assert hvd_trace.TAP is hvd_trace.NULL_TAP  # the SAME object


def test_activate_from_env(monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE", "1")
    assert hvd_trace.activate_from_env()
    monkeypatch.setenv("HOROVOD_TRACE", "0")
    monkeypatch.delenv("HOROVOD_TRACE_DIR", raising=False)
    assert not hvd_trace.activate_from_env()
    # A trace dir alone arms the (always-on) flight recorder.
    monkeypatch.setenv("HOROVOD_TRACE_DIR", "/tmp/somewhere")
    assert hvd_trace.activate_from_env()


# ------------------------------------------------------------- recording
def test_wrap_step_enters_the_profilers_step_annotation(monkeypatch):
    """Armed, every call of the wrapped step lies inside a
    ``jax.profiler.StepTraceAnnotation("hvd_step", step_num=idx)``, so a
    profiler session holds the program's step span on the device's clock;
    off, nothing is wrapped at all."""
    import jax.profiler

    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            self.rec = [name, kw, "made"]
            seen.append(self.rec)

        def __enter__(self):
            self.rec[2] = "entered"

        def __exit__(self, *exc):
            self.rec[2] = "left"

    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Annotation)

    def f():
        # the step runs INSIDE the annotation
        return [list(r) for r in seen]

    assert hvd_trace.wrap_step(f) is f and not seen
    hvd_trace.install(True)
    step = hvd_trace.wrap_step(f)
    assert step is not f
    assert step()[-1] == ["hvd_step", {"step_num": 0}, "entered"]
    step()
    assert seen == [["hvd_step", {"step_num": 0}, "left"],
                    ["hvd_step", {"step_num": 1}, "left"]]
    steps = [e["args"]["step"] for e in hvd_trace.TAP.window()["events"]
             if e["name"] == "hvd_step"]
    assert steps == [0, 1]


def test_wrap_step_records_spans_with_meta_and_plan_args():
    hvd_trace.install(True)
    hvd_trace.note_plan(topo_algorithm="ring", wire_dtype="int8")

    calls = []
    step = hvd_trace.wrap_step(lambda x: calls.append(x), overlap=True)
    step(1)
    step(2)
    assert calls == [1, 2]
    win = hvd_trace.TAP.window()
    spans = [e for e in win["events"] if e["name"] == "hvd_step"]
    assert len(spans) == 2
    assert [s["args"]["step"] for s in spans] == [0, 1]
    # Build meta AND the noted correlation ids ride every span.
    assert spans[0]["args"]["overlap"] is True
    assert spans[0]["args"]["topo_algorithm"] == "ring"
    assert spans[0]["args"]["wire_dtype"] == "int8"
    assert len(win["steps"]) == 2


def test_ring_is_bounded():
    tap = hvd_trace.TraceTap(ring_capacity=16)
    for i in range(100):
        tap.event(f"e{i}")
    win = tap.window()
    assert len(win["events"]) == 16
    assert win["events"][-1]["name"] == "e99"


def test_commit_step_spans_between_commits_and_defers_to_wrapped():
    hvd_trace.install(True)
    tap = hvd_trace.TAP
    tap.commit_step()
    tap.commit_step()
    tap.commit_step()
    # N commits = N-1 inter-commit step spans in the skew feed.
    assert len(tap.window()["steps"]) == 2
    # With a wrapped step recording real spans, commits become plain
    # markers — no double counting.
    hvd_trace.install(True)
    tap = hvd_trace.TAP
    step = hvd_trace.wrap_step(lambda: None)
    step()
    tap.commit_step()
    tap.commit_step()
    assert len(tap.window()["steps"]) == 1


def test_span_event_and_timeline_mirror():
    hvd_trace.install(True)
    rec = hvd_trace.TAP.event("phase_x", ph="X", cat="op", dur=0.5, foo=3)
    assert rec["dur"] == 0.5 and rec["args"] == {"foo": 3}
    hvd_trace.TAP.timeline_event(
        {"name": "NEGOTIATE_ALLREDUCE", "ph": "B", "pid": 0, "tid": 4}
    )
    names = [e["name"] for e in hvd_trace.TAP.window()["events"]]
    assert "phase_x" in names and "NEGOTIATE_ALLREDUCE" in names


# -------------------------------------------------------- flight recorder
def test_flight_dump_atomic_and_counted(tmp_path):
    hvd_metrics.install(True)
    hvd_trace.install(True)
    hvd_trace.TAP.event("before_death", cat="op")
    path = hvd_trace.TAP.flight_dump("unit-test", directory=str(tmp_path))
    assert path and os.path.exists(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "unit-test"
    assert doc["schema"] == hvd_trace.SCHEMA
    assert any(e["name"] == "before_death" for e in doc["events"])
    assert "dumped_at" in doc and "clock" in doc
    flat = hvd_metrics()
    assert flat['hvd_trace_flight_dumps_total{reason="unit-test"}'] == 1.0
    # No leftover temp files (checkpoint.py atomic-write discipline).
    assert all(".tmp." not in fn for fn in os.listdir(tmp_path))


def test_flight_dump_without_dir_is_safe(monkeypatch):
    monkeypatch.delenv("HOROVOD_TRACE_DIR", raising=False)
    hvd_trace.install(True)
    assert hvd_trace.TAP.flight_dump("no-dir") is None


def test_excepthook_dumps_on_uncaught(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE_DIR", str(tmp_path))
    hvd_trace.install(True)
    assert sys.excepthook is hvd_trace._excepthook
    hvd_trace.TAP.event("last_words")
    # Drive the hook directly (raising through the interpreter would
    # kill the test process).
    try:
        raise RuntimeError("boom")
    except RuntimeError:
        hvd_trace._excepthook(*sys.exc_info())
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight.")]
    assert dumps, "uncaught crash did not dump the flight ring"
    with open(tmp_path / dumps[0]) as f:
        assert json.load(f)["reason"] == "crash:RuntimeError"
    hvd_trace.reset()
    assert sys.excepthook is not hvd_trace._excepthook


def test_sigterm_notice_dumps_flight_ring(tmp_path, monkeypatch):
    from horovod_tpu.fault import preemption

    monkeypatch.setenv("HOROVOD_TRACE_DIR", str(tmp_path))
    hvd_trace.install(True)
    preemption.clear()
    try:
        preemption.request_preemption("SIGTERM")
        dumps = [
            f for f in os.listdir(tmp_path) if f.startswith("flight.")
        ]
        assert dumps, "preemption notice did not dump the flight ring"
        with open(tmp_path / dumps[0]) as f:
            assert json.load(f)["reason"].startswith("preempt:")
    finally:
        preemption.clear()


# ------------------------------------------------- clock offset + pusher
def test_clock_endpoint_and_offset_estimate():
    from horovod_tpu.run.http_server import KVStoreServer

    srv = KVStoreServer(port=0)
    srv.start()
    try:
        est = tpush.estimate_clock_offset("127.0.0.1", srv.port)
        assert est is not None
        # Same host, same clock: the offset is bounded by the RTT.
        assert est["rtt_s"] > 0
        assert abs(est["offset_s"]) <= max(est["rtt_s"], 0.05)
    finally:
        srv.stop()


def test_clock_estimate_unreachable_returns_none():
    assert tpush.estimate_clock_offset("127.0.0.1", 1, pings=1) is None


def test_pusher_ships_window_and_event_log():
    from horovod_tpu.run.http_server import KVStoreServer

    srv = KVStoreServer(port=0)
    srv.start()
    hvd_trace.install(True)
    hvd_trace.TAP.event("shipped", cat="op")
    try:
        p = tpush.TracePusher("127.0.0.1", srv.port, rank=3, interval=60)
        p.push_once()
        doc = tpush.decode_window(srv.snapshot(hvd_trace.KV_SCOPE)["rank.3"])
        assert doc is not None
        assert doc["clock"]["estimated"] is True
        assert any(e["name"] == "shipped" for e in doc["events"])
        assert "event_log" in doc
        p.stop()
    finally:
        srv.stop()
    assert tpush.decode_window(b"\xff junk") is None


# ----------------------------------------------------- skew attribution
def test_skew_tracker_attributes_worst_rank_once():
    t = 1000.0
    d0 = {"steps": [[0, t, t + 0.01], [1, t + 1, t + 1.01]]}
    d1 = {"steps": [[0, t, t + 0.21], [1, t + 1, t + 1.02]]}
    sk = tpush.StepSkewTracker(threshold_s=0.05)
    out = sk.update({0: d0, 1: d1})
    assert [(i, w) for i, _, w in out] == [(0, 1), (1, 1)]
    assert abs(out[0][1] - 0.20) < 1e-9
    assert abs(out[1][1] - 0.01) < 1e-9
    # Cumulative windows re-observed: charged exactly once.
    assert sk.update({0: d0, 1: d1}) == []
    # A later step flows through normally.
    d0["steps"].append([2, t + 2, t + 2.0])
    d1["steps"].append([2, t + 2, t + 2.5])
    out = sk.update({0: d0, 1: d1})
    assert [(i, w) for i, _, w in out] == [(2, 1)]


def test_skew_tracker_waits_for_all_ranks_and_single_rank_noop():
    sk = tpush.StepSkewTracker(threshold_s=0.01)
    d0 = {"steps": [[0, 0.0, 0.5], [1, 1.0, 1.5]]}
    assert sk.update({0: d0}) == []  # one rank: nothing to compare
    d1 = {"steps": [[0, 0.0, 0.6]]}  # rank 1 has not finished step 1 yet
    out = sk.update({0: d0, 1: d1})
    assert [i for i, _, _ in out] == [0]


# ------------------------------------------------------------ merge
def _window(rank, t, dur=0.01, extra_events=()):
    return {
        "schema": 1,
        "rank": rank,
        "clock": {"offset_s": 0.001, "rtt_s": 0.002, "estimated": True},
        "plan": {},
        "events": [
            {"name": "hvd_step", "ph": "X", "ts": t, "dur": dur,
             "cat": "step", "tid": 0, "args": {"step": 0}},
            *extra_events,
        ],
        "steps": [[0, t, t + dur]],
        "event_log": [
            {"seq": 1, "site": "step", "hit": 4, "action": "delay",
             "detail": "", "rank": rank},
        ],
    }


def test_merge_windows_lanes_clock_and_determinism():
    t = 1700000000.0
    ranks = {0: _window(0, t), 1: _window(1, t, dur=0.2)}
    driver = {
        "schema": 1, "rank": -1, "clock": {}, "plan": {},
        "events": [
            {"name": "hvd_generation_publish", "ph": "i", "ts": t,
             "cat": "driver", "tid": 0, "args": {"gen": 1}},
        ],
        "steps": [],
    }
    doc = tmerge.merge_windows(ranks, driver)
    events = doc["traceEvents"]
    lanes = {
        e["args"]["name"] for e in events
        if e.get("name") == "process_name"
    }
    assert lanes == {"rank 0", "rank 1", "driver"}
    # The driver's lane sorts above any plausible rank pid.
    pub = [e for e in events if e["name"] == "hvd_generation_publish"]
    assert pub and pub[0]["pid"] == tmerge.DRIVER_PID
    # Per-lane clock metadata: recorded, not applied.
    clocks = [e for e in events if e["name"] == "hvd_clock_offset"]
    assert {e["pid"] for e in clocks} >= {0, 1}
    assert all("not applied" in e["args"]["note"] for e in clocks)
    # Fault event-log lines ride their own virtual thread.
    delays = [e for e in events if e["name"] == "step:delay"]
    assert len(delays) == 2
    assert all(e["tid"] == tmerge.TID_EVENT_LOG for e in delays)
    # Timestamps are microseconds relative to the earliest event.
    steps = [e for e in events if e["name"] == "hvd_step"]
    assert min(e["ts"] for e in steps) == 0.0
    assert any(abs(e["dur"] - 200000.0) < 1e-6 for e in steps)
    # Deterministic bytes for identical inputs.
    a = json.dumps(doc, sort_keys=True)
    b = json.dumps(tmerge.merge_windows(ranks, driver), sort_keys=True)
    assert a == b


def test_merge_postmortem_death_markers_and_window_trim():
    t = 1700000000.0
    dumps = {
        0: dict(_window(0, t), reason="guard-abort", dumped_at=t + 30.0,
                events=[
                    {"name": "old", "ph": "i", "ts": t, "cat": "op",
                     "tid": 0},
                    {"name": "recent", "ph": "i", "ts": t + 29.0,
                     "cat": "op", "tid": 0},
                ],
                steps=[[0, t, t + 0.01], [7, t + 29, t + 29.01]]),
        1: dict(_window(1, t), reason="stall-shutdown",
                dumped_at=t + 31.0),
    }
    doc = tmerge.merge_postmortem(dumps, window_s=10.0)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "DEATH:guard-abort" in names
    assert "DEATH:stall-shutdown" in names
    # The 10s window trimmed rank 0's stale events/steps.
    assert "recent" in names and "old" not in names
    reasons = doc["otherData"]["postmortem"]["reasons"]
    assert reasons == {"0": "guard-abort", "1": "stall-shutdown"}


def test_trace_merge_cli_roundtrip(tmp_path):
    t = 1700000000.0
    for r in (0, 1):
        with open(tmp_path / f"rank.{r}.json", "w") as f:
            json.dump(_window(r, t), f)
    with open(tmp_path / "flight.rank0.json", "w") as f:
        json.dump(
            dict(_window(0, t), reason="guard-abort", dumped_at=t + 1),
            f,
        )
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_merge as cli
    finally:
        sys.path.pop(0)
    assert cli.main([str(tmp_path)]) == 0
    with open(tmp_path / "merged_trace.json") as f:
        doc = json.load(f)
    assert {e["pid"] for e in doc["traceEvents"]} == {0, 1}
    assert cli.main([str(tmp_path), "--postmortem"]) == 0
    with open(tmp_path / "postmortem_trace.json") as f:
        pm = json.load(f)
    assert any(
        e["name"] == "DEATH:guard-abort" for e in pm["traceEvents"]
    )
    # Empty dir: a clear error, not a stack trace.
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli.main([str(empty)]) == 1
    assert cli.main([str(tmp_path / "missing")]) == 2


def test_read_flight_dumps_prefers_driver_bundle(tmp_path):
    with open(tmp_path / "flight.rank0.json", "w") as f:
        json.dump({"rank": 0, "reason": "raw"}, f)
    dumps = tmerge.read_flight_dumps(str(tmp_path))
    assert dumps[0]["reason"] == "raw"
    with open(tmp_path / "postmortem.json", "w") as f:
        json.dump(
            {"dumps": [{"rank": 0, "reason": "bundled"}]}, f
        )
    dumps = tmerge.read_flight_dumps(str(tmp_path))
    assert dumps[0]["reason"] == "bundled"


def test_load_chrome_trace_tolerates_unterminated(tmp_path):
    p = tmp_path / "partial.json"
    p.write_text('[\n{"name": "A", "ph": "B"},\n{"name": "A", "ph": "E"}')
    events = tmerge.load_chrome_trace(str(p))
    assert [e["ph"] for e in events] == ["B", "E"]


# ------------------------------------------------ compiled-path step tap
def test_make_train_step_zero_overhead_and_traced(devices):
    import jax.numpy as jnp
    import optax

    import horovod_tpu.jax as hvdj
    from horovod_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"data": 8})
    params = {"w": jnp.ones((4,), jnp.float32)}
    batch = jnp.ones((8, 4), jnp.float32)

    def loss_fn(p, b):
        return jnp.mean((b * p["w"]) ** 2)

    tx = optax.sgd(0.1)
    # Disabled: the returned step function is the raw jitted callable —
    # no wrapper attribute, nothing recorded.
    step = hvdj.make_train_step(loss_fn, tx, mesh, donate=False)
    assert not hasattr(step, "__hvd_trace_wrapped__")

    hvd_trace.install(True)
    traced = hvdj.make_train_step(
        loss_fn, tx, mesh, donate=False, quantized=True
    )
    assert getattr(traced, "__hvd_trace_wrapped__", False)
    opt_state = tx.init(params)
    traced(params, opt_state, batch)
    win = hvd_trace.TAP.window()
    spans = [e for e in win["events"] if e["name"] == "hvd_step"]
    assert len(spans) == 1
    args = spans[0]["args"]
    assert args["step"] == 0
    assert args["wire_dtype"] == "int8"
    assert args["op"] == "AVERAGE"
    # The fusion layer noted its bucket plan at trace time.
    assert args.get("fusion_path")


def test_distributed_optimizer_notes_plan_when_tracing():
    import optax

    import horovod_tpu.jax as hvdj

    hvd_trace.install(True)
    hvdj.DistributedOptimizer(optax.sgd(0.1), quantized=True)
    plan = hvd_trace.TAP.window()["plan"]
    assert plan["optimizer"] == "DistributedOptimizer"
    assert plan["wire_dtype"] == "int8"


def test_flash_kernel_notes_its_plan_once_per_compile(monkeypatch):
    """The forward flash kernel's tile plan is a trace-time plan note: one
    emission per compile, tracing armed or not."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_attention as pa

    q = jnp.zeros((8, 64, 16), jnp.float32)
    fresh = lambda: jax.jit(functools.partial(pa.flash_attention, causal=True))
    fresh()(q, q, q)
    assert hvd_trace.TAP is hvd_trace.NULL_TAP
    assert hvd_trace.plan_args()["flash_block_q"] == 64   # noted all the same

    hvd_trace.reset_build_ledger()
    notes = []
    note_plan = hvd_trace.note_plan
    monkeypatch.setattr(
        hvd_trace, "note_plan",
        lambda **kw: (notes.append(kw), note_plan(**kw)),
    )
    monkeypatch.setattr(pa, "_PREF_BLOCK", 16)
    step = fresh()
    step(q, q, q)
    step(q, q, q)                       # the cached executable: no new note
    assert len(notes) == 1
    assert hvd_trace.plan_args() == {
        "flash_block_q": 16, "flash_block_k": 16, "flash_rows_per_step": 8,
        "flash_grid_steps": 16, "flash_pairs_visited": 0.625,
    }


def test_flash_backward_notes_its_plan_beside_the_forwards(monkeypatch):
    """The backward kernels' plan is noted when the gradient is traced,
    under names of its own, once per compile."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_attention as pa

    q = jnp.zeros((8, 64, 16), jnp.float32)
    attn = functools.partial(pa.flash_attention, causal=True)
    notes = []
    note_plan = hvd_trace.note_plan
    monkeypatch.setattr(
        hvd_trace, "note_plan",
        lambda **kw: (notes.append(kw), note_plan(**kw)),
    )
    monkeypatch.setattr(pa, "_PREF_BLOCK", 16)
    grad = jax.jit(jax.grad(lambda q, k, v: attn(q, k, v).sum(),
                            argnums=(0, 1, 2)))
    grad(q, q, q)
    grad(q, q, q)                       # the cached executable: no new note
    assert [sorted(n)[0] for n in notes] == ["flash_block_k",
                                             "flash_bwd_block_k"]
    plan = hvd_trace.plan_args()
    assert {k: v for k, v in plan.items() if k.startswith("flash_bwd")} == {
        "flash_bwd_block_q": 16, "flash_bwd_block_k": 16,
        "flash_bwd_rows_per_step": 8,
        # a whole dq of 8 rows fits: one kernel, 1 x 4 q blocks x 4 k blocks
        "flash_bwd_one_pass": True,
        "flash_bwd_grid_steps": 16, "flash_bwd_pairs_visited": 0.625,
        "flash_bwd_vmem_mb": 1.6,      # what that step counts in VMEM
    }
    assert plan["flash_grid_steps"] == 16


# --------------------------------------------------- timeline satellites
def test_timeline_writer_crash_warns_once_and_counts_drops(caplog):
    hvd_metrics.install(True)
    w = TimelineWriter(
        os.path.join("/nonexistent_dir_hvd_trace_test", "t.json")
    )
    w._thread.join(timeout=5.0)
    assert not w._thread.is_alive()
    assert not w._healthy
    with caplog.at_level(logging.WARNING, logger="horovod_tpu.timeline"):
        w.enqueue({"name": "a"})
        w.enqueue({"name": "b"})
    assert w.dropped == 2
    flat = hvd_metrics()
    assert flat["hvd_timeline_dropped_total"] == 2.0
    # One-shot warning NAMES the original exception.
    warnings = [
        r for r in caplog.records if "dropping events" in r.getMessage()
    ]
    assert len(warnings) == 1
    assert "nonexistent_dir_hvd_trace_test" in warnings[0].getMessage()


def test_timeline_writer_crash_counts_queued_backlog(tmp_path):
    """Events already queued when the writer dies are lost too — they
    must be counted, not silently forgotten."""
    hvd_metrics.install(True)
    gate = threading.Event()

    class GatedWriter(TimelineWriter):
        def _run(self):
            gate.wait(5.0)
            TimelineWriter._run(self)

    w = GatedWriter(str(tmp_path / "no_such_dir" / "t.json"))
    for i in range(5):
        w.enqueue({"name": f"e{i}"})
    gate.set()
    w._thread.join(timeout=5.0)
    assert w.dropped == 5
    assert hvd_metrics()["hvd_timeline_dropped_total"] == 5.0


def test_timeline_shutdown_join_timeout_detected(tmp_path, caplog):
    hvd_metrics.install(True)
    release = threading.Event()

    class StuckWriter(TimelineWriter):
        def _run(self):
            release.wait(10.0)
            TimelineWriter._run(self)

    w = StuckWriter(str(tmp_path / "t.json"))
    for i in range(3):
        w.enqueue({"name": f"e{i}"})
    with caplog.at_level(logging.WARNING, logger="horovod_tpu.timeline"):
        w.shutdown(timeout=0.2)
    assert any(
        "still alive" in r.getMessage() for r in caplog.records
    ), "silent return with the thread still alive"
    assert w.dropped >= 3
    assert hvd_metrics()["hvd_timeline_dropped_total"] >= 3.0
    release.set()
    w._thread.join(timeout=5.0)


def test_timeline_emit_mirrors_into_trace_ring(tmp_path):
    hvd_trace.install(True)
    tl = Timeline()
    tl.initialize(str(tmp_path / "t.json"), rank=0)
    tl.start("tensor_a", "XLA_ALLREDUCE")
    tl.end("tensor_a", "XLA_ALLREDUCE")
    tl.shutdown()
    names = [
        e["name"] for e in hvd_trace.TAP.window()["events"]
        if e["cat"] == "timeline"
    ]
    assert "XLA_ALLREDUCE" in names


# -------------------------------------- timeline runtime-control contract
def test_start_stop_timeline_restart_cycle_two_loadable_traces(tmp_path):
    """hvd.start_timeline/stop_timeline restart cycle: both sessions
    produce independently loadable traces with their own events."""
    hvd.shutdown()
    hvd.init()
    try:
        p1, p2 = str(tmp_path / "t1.json"), str(tmp_path / "t2.json")
        hvd.start_timeline(p1)
        hvd.allreduce(np.ones(4, np.float32), name="tl.restart.a")
        hvd.stop_timeline()
        hvd.start_timeline(p2)
        hvd.allreduce(np.ones(4, np.float32), name="tl.restart.b")
        hvd.stop_timeline()
        for path, tensor in ((p1, "tl.restart.a"), (p2, "tl.restart.b")):
            events = tmerge.load_chrome_trace(path)
            names = {e.get("name") for e in events}
            assert "NEGOTIATE_ALLREDUCE" in names, path
            lanes = {
                e.get("args", {}).get("name")
                for e in events if e.get("ph") == "M"
            }
            assert tensor in lanes, (path, lanes)
        # The second file must not contain the first session's tensor.
        names2 = {
            e.get("args", {}).get("name")
            for e in tmerge.load_chrome_trace(p2) if e.get("ph") == "M"
        }
        assert "tl.restart.a" not in names2
    finally:
        hvd.shutdown()


def test_second_start_timeline_rejected_while_active(tmp_path):
    hvd.shutdown()
    hvd.init()
    try:
        hvd.start_timeline(str(tmp_path / "t1.json"))
        with pytest.raises(ValueError, match="already active"):
            hvd.start_timeline(str(tmp_path / "t2.json"))
        hvd.stop_timeline()
        # After stop, a new session is accepted again.
        hvd.start_timeline(str(tmp_path / "t3.json"))
        hvd.stop_timeline()
    finally:
        hvd.shutdown()


def test_plan_activity_events_carry_documented_correlation_id(tmp_path):
    """docs/timeline.md promises every executed plan's activity events
    carry ``{"args": {"plan": "hvd_plan_<id>"}}`` — assert it on a real
    trace (native core; the pure-Python fallback has no plan ids)."""
    hvd.shutdown()
    hvd.init()
    try:
        from horovod_tpu.core.native_runtime import NativeRuntime

        if not isinstance(hvd._runtime, NativeRuntime):
            pytest.skip("native core unavailable; plan ids are native")
        path = str(tmp_path / "plans.json")
        hvd.start_timeline(path)
        hvd.allreduce(np.ones(8, np.float32), name="tl.plan.tensor")
        hvd.stop_timeline()
        events = tmerge.load_chrome_trace(path)
        plan_ids = {
            e["args"]["plan"]
            for e in events
            if e.get("ph") == "B" and "plan" in e.get("args", {})
        }
        assert plan_ids, "no activity event carried a plan id"
        assert all(
            re.fullmatch(r"hvd_plan_\d+", p) for p in plan_ids
        ), plan_ids
    finally:
        hvd.shutdown()


def test_native_plan_trace_event_matches_timeline_ids(tmp_path):
    """The fleet-trace ring's hvd_plan span carries the SAME
    hvd_plan_<id> string the native timeline stamps — the step → plan →
    collective link one id ties together."""
    hvd.shutdown()
    hvd_trace.install(True)
    hvd.init()
    try:
        from horovod_tpu.core.native_runtime import NativeRuntime

        if not isinstance(hvd._runtime, NativeRuntime):
            pytest.skip("native core unavailable")
        hvd.allreduce(np.ones(8, np.float32), name="tl.plan.trace")
        deadline = time.monotonic() + 5.0
        plans = []
        while time.monotonic() < deadline and not plans:
            plans = [
                e for e in hvd_trace.TAP.window()["events"]
                if e["name"] == "hvd_plan"
            ]
            time.sleep(0.05)
        assert plans, "no hvd_plan span reached the trace ring"
        assert re.fullmatch(
            r"hvd_plan_\d+", plans[-1]["args"]["plan"]
        )
        assert plans[-1]["args"]["op"] == "ALLREDUCE"
    finally:
        hvd.shutdown()
