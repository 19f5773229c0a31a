"""Metrics subsystem (horovod_tpu/metrics): registry semantics, the
zero-overhead disabled tap, Prometheus rendering/parsing, driver-side
aggregation over the KV plane, the satellite fixes that rode along, and a
2-rank end-to-end scrape through the real elastic driver
(docs/metrics.md is the prose companion)."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import pytest

from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.metrics import export as mexport
from horovod_tpu.metrics import registry as mreg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_metrics_state():
    """Every test starts and ends with the tap in its env-default state
    (inactive in the test environment)."""
    hvd_metrics.reset()
    yield
    hvd_metrics.reset()


# ---------------------------------------------------------------- registry
def test_histogram_bucket_edges():
    h = mreg.Histogram("h", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01):   # <= 0.01 bucket
        h.observe(v)
    h.observe(0.05)           # <= 0.1
    h.observe(0.5)            # <= 1.0
    h.observe(2.0)            # +Inf overflow
    (series,) = h.snapshot()["series"]
    assert series["buckets"] == [2, 1, 1, 1]
    assert series["count"] == 5
    assert abs(series["sum"] - 2.565) < 1e-9
    assert h.snapshot()["bucket_edges"] == [0.01, 0.1, 1.0]


def test_histogram_labels_and_count():
    h = mreg.Histogram("h", buckets=(1.0,))
    h.observe(0.5, op="A")
    h.observe(0.5, op="A")
    h.observe(3.0, op="B")
    assert h.count(op="A") == 2
    assert h.count(op="B") == 1
    assert h.count(op="C") == 0


def test_counter_concurrent_increments():
    c = mreg.Counter("c")
    n_threads, per_thread = 8, 5000

    def work():
        for _ in range(per_thread):
            c.inc(1, op="x")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(op="x") == n_threads * per_thread


def test_counter_rejects_negative_and_type_clash():
    r = mreg.Registry()
    with pytest.raises(ValueError):
        r.counter("c").inc(-1)
    r.counter("same")
    with pytest.raises(TypeError):
        r.gauge("same")


def test_gauge_set_overwrites():
    g = mreg.Gauge("g")
    g.set(3, shard="a")
    g.set(7, shard="a")
    assert g.value(shard="a") == 7


# ------------------------------------------------------------ tap discipline
def test_disabled_tap_is_shared_noop_singleton():
    assert not hvd_metrics.ACTIVE
    assert hvd_metrics.TAP is hvd_metrics.NULL_TAP
    assert hvd_metrics.tap() is hvd_metrics.NULL_TAP
    # No-ops never record anything.
    hvd_metrics.TAP.inc("hvd_rpc_retries_total")
    hvd_metrics.TAP.observe("hvd_op_execute_seconds", 1.0, op="X")
    hvd_metrics.TAP.set("hvd_queue_depth", 9)
    assert hvd_metrics.snapshot() == {}

    import horovod_tpu as hvd

    assert hvd.metrics() == {}
    assert hvd.metrics_snapshot() == {}


def test_activation_installs_live_tap_and_reset_restores_singleton():
    hvd_metrics.install(True)
    assert hvd_metrics.ACTIVE
    assert hvd_metrics.TAP is not hvd_metrics.NULL_TAP
    hvd_metrics.TAP.inc("hvd_rpc_retries_total", request="Ping")
    snap = hvd_metrics.snapshot()
    assert snap["hvd_rpc_retries_total"]["type"] == "counter"
    # Pre-seeded zero families surface even when they never fired.
    assert "hvd_stall_warnings_total" in snap
    hvd_metrics.reset()
    assert hvd_metrics.TAP is hvd_metrics.NULL_TAP  # the SAME object


def test_activate_from_env(monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS", "1")
    assert hvd_metrics.activate_from_env()
    monkeypatch.setenv("HOROVOD_METRICS", "0")
    assert not hvd_metrics.activate_from_env()
    assert hvd_metrics.TAP is hvd_metrics.NULL_TAP


def test_callable_module_returns_flat_dict():
    hvd_metrics.install(True)
    hvd_metrics.TAP.inc("hvd_plans_total", 3, op="ALLREDUCE")
    flat = hvd_metrics()  # the hvd.metrics() surface
    assert flat['hvd_plans_total{op="ALLREDUCE"}'] == 3.0


# ------------------------------------------------------------------ export
def _sample_snapshot():
    tap = hvd_metrics.MetricsTap()
    tap.inc("hvd_rpc_retries_total", 2, request="Ping")
    tap.set("hvd_queue_depth", 4)
    tap.observe("hvd_op_execute_seconds", 0.002, op="ALLREDUCE")
    tap.observe("hvd_op_execute_seconds", 0.2, op="ALLREDUCE")
    return tap.snapshot()


def test_render_parse_roundtrip_with_rank_labels():
    snap = _sample_snapshot()
    text = mexport.render_prometheus(
        [({"rank": "0"}, snap), ({"rank": "1"}, snap)]
    )
    parsed = mexport.parse_prometheus(text)
    assert parsed["hvd_rpc_retries_total"]["type"] == "counter"
    ranks = {
        labels["rank"]
        for _, labels, _ in parsed["hvd_rpc_retries_total"]["samples"]
    }
    assert ranks == {"0", "1"}
    # Histogram samples are filed under the base name; cumulative buckets
    # end at the series count.
    hist = parsed["hvd_op_execute_seconds"]
    assert hist["type"] == "histogram"
    counts = {
        (labels["rank"]): v
        for name, labels, v in hist["samples"]
        if name.endswith("_count")
    }
    assert counts == {"0": 2.0, "1": 2.0}
    inf_buckets = [
        v for name, labels, v in hist["samples"]
        if name.endswith("_bucket") and labels["le"] == "+Inf"
    ]
    assert all(v == 2.0 for v in inf_buckets)


def test_render_cumulative_bucket_monotonicity():
    snap = _sample_snapshot()
    text = mexport.render_prometheus([({}, snap)])
    parsed = mexport.parse_prometheus(text)
    series = [
        (float("inf") if labels["le"] == "+Inf" else float(labels["le"]), v)
        for name, labels, v in parsed["hvd_op_execute_seconds"]["samples"]
        if name.endswith("_bucket")
    ]
    series.sort()
    values = [v for _, v in series]
    assert values == sorted(values), "buckets must be cumulative"
    assert values[-1] == 2.0


def test_render_drops_mismatched_histogram_edges():
    t1 = hvd_metrics.MetricsTap()
    t1.registry.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
    t2 = hvd_metrics.MetricsTap()
    t2.registry.histogram("h", buckets=(5.0,)).observe(0.5)
    text = mexport.render_prometheus(
        [({"rank": "0"}, t1.snapshot()), ({"rank": "1"}, t2.snapshot())]
    )
    parsed = mexport.parse_prometheus(text)
    ranks = {
        labels.get("rank")
        for name, labels, _ in parsed["h"]["samples"]
        if name.endswith("_count")
    }
    assert ranks == {"0"}  # the latecomer was dropped, not corrupted


def test_label_escaping_roundtrip():
    tap = hvd_metrics.MetricsTap()
    tap.inc("c_total", 1, path='a"b\\c')
    text = mexport.render_prometheus([({}, tap.snapshot())])
    parsed = mexport.parse_prometheus(text)
    ((_, labels, value),) = parsed["c_total"]["samples"]
    assert value == 1.0
    assert labels["path"] == 'a"b\\c'


def test_aggregate_kv_snapshots_skips_garbage():
    snap = _sample_snapshot()
    entries = {
        "rank.0": json.dumps(
            {"labels": {"rank": "0"}, "snapshot": snap}
        ).encode(),
        "rank.1": b"\xff not json",
    }
    text = mexport.aggregate_kv_snapshots(entries)
    parsed = mexport.parse_prometheus(text)
    assert "hvd_rpc_retries_total" in parsed


# --------------------------------------------------- /metrics on KV server
def test_kv_server_serves_prometheus_text():
    from horovod_tpu.run.http_server import KVStoreClient, KVStoreServer

    hvd_metrics.install(True)
    hvd_metrics.TAP.inc("hvd_elastic_generations_total")
    server = KVStoreServer()
    server.start()
    try:
        kv = KVStoreClient("127.0.0.1", server.port)
        worker_snap = _sample_snapshot()
        kv.put(
            mexport.KV_SCOPE, "rank.1",
            json.dumps(
                {"labels": {"rank": "1"}, "snapshot": worker_snap}
            ).encode(),
        )
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=10
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        parsed = mexport.parse_prometheus(text)
        # The serving process's registry carries the driver-role label...
        gens = parsed["hvd_elastic_generations_total"]["samples"]
        assert any(labels.get("role") == "driver" for _, labels, _ in gens)
        # ...and the pushed worker snapshot its rank label.
        execs = parsed["hvd_op_execute_seconds"]["samples"]
        assert any(labels.get("rank") == "1" for _, labels, _ in execs)
        # The ordinary KV surface still works next to /metrics.
        kv.put("scope", "k", b"v")
        assert kv.get("scope", "k") == b"v"
    finally:
        server.stop()


# ------------------------------------------------------- satellite fixes
def test_respawn_drain_grace_scales_with_detection_windows():
    from horovod_tpu.run.elastic_driver import _respawn_drain_grace

    # Defaults: 2x the 10s heartbeat + 5s margin.
    assert _respawn_drain_grace({}) == 25.0
    # Never below the base scale-down grace.
    assert _respawn_drain_grace(
        {"HOROVOD_ELASTIC_HEARTBEAT_S": "1"}, base=15.0
    ) == 15.0
    # A configured stall window dominates when longer.
    assert _respawn_drain_grace(
        {"HOROVOD_STALL_ABORT_TIME_SECONDS": "60"}
    ) == 65.0
    assert _respawn_drain_grace(
        {"HOROVOD_ELASTIC_HEARTBEAT_S": "40",
         "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "30"}
    ) == 85.0
    # Malformed values fall back instead of raising in the driver.
    assert _respawn_drain_grace(
        {"HOROVOD_ELASTIC_HEARTBEAT_S": "nope"}
    ) == 25.0


def test_warn_if_unrestored_gen_gt_1(monkeypatch, caplog):
    import logging

    from horovod_tpu.elastic import _warn_if_unrestored

    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "3")
    monkeypatch.delenv("HOROVOD_ELASTIC_REQUIRE_SNAPSHOT", raising=False)
    with caplog.at_level(logging.ERROR, logger="horovod_tpu.elastic"):
        _warn_if_unrestored(False)
    assert any("no restored snapshot" in r.message for r in caplog.records)
    # Restored, or a genuine first start: silent.
    caplog.clear()
    _warn_if_unrestored(True)
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "1")
    _warn_if_unrestored(False)
    assert not caplog.records
    # The knob upgrades the warning to a hard failure.
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "2")
    monkeypatch.setenv("HOROVOD_ELASTIC_REQUIRE_SNAPSHOT", "1")
    with pytest.raises(RuntimeError, match="no restored snapshot"):
        _warn_if_unrestored(False)


def test_probe_free_port_local():
    from horovod_tpu.run.elastic_driver import ElasticDriver

    drv = ElasticDriver.__new__(ElasticDriver)  # no __init__: unit scope
    drv._ssh_port = None
    port = drv._probe_free_port("localhost")
    assert 0 < port < 65536


def test_inline_sync_core_down_wakes_executor_drain():
    """Satellite (native_runtime): an inline synchronize() that observes
    next_plan == -1 must signal the parked executor thread so orphaned
    entry callbacks are drained promptly — not only after every waiter
    leaves."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init()
    rt = hvd._runtime
    from horovod_tpu.core.native_runtime import NativeRuntime

    if not isinstance(rt, NativeRuntime):
        hvd.shutdown()
        pytest.skip("native core unavailable")
    assert not rt._core_down.is_set()
    hvd.allreduce(np.ones(4, np.float32), name="warm")  # consumer works
    # Simulate the core dying under a parked executor: shut the core down
    # (FailAll + next_plan == -1) while a fake waiter keeps the executor
    # parked, then drive the inline-consumer branch once.
    with rt._cv:
        rt._sync_waiters += 1
        rt._no_waiters.clear()
    try:
        rt.core.shutdown()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not rt._core_down.is_set():
            with rt._consumer_lock:
                plan = rt.core.next_plan(timeout_ms=10)
                if plan == -1:
                    rt._core_down.set()
                    rt._no_waiters.set()
            time.sleep(0.01)
        assert rt._core_down.is_set()
        # The executor thread must exit its park and run the finally
        # drain even though a synchronize() waiter still exists.
        rt._thread.join(timeout=5.0)
        assert not rt._thread.is_alive()
    finally:
        with rt._cv:
            rt._sync_waiters -= 1
        hvd.shutdown()


# ------------------------------------------------------------- dump CLI
def test_metrics_dump_pretty_and_diff(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_dump
    finally:
        sys.path.pop(0)

    t = hvd_metrics.MetricsTap()
    t.inc("hvd_plans_total", 2, op="ALLREDUCE")
    t.observe("hvd_op_execute_seconds", 0.25, op="ALLREDUCE")
    a = tmp_path / "a.json"
    a.write_text(json.dumps(t.snapshot()))
    t.inc("hvd_plans_total", 3, op="ALLREDUCE")
    b = tmp_path / "b.json"
    b.write_text(json.dumps(t.snapshot()))

    assert metrics_dump.main([str(a)]) == 0
    out = capsys.readouterr().out
    assert 'hvd_plans_total{op="ALLREDUCE"}' in out
    assert "count=1" in out

    assert metrics_dump.main([str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "+3" in out


# ------------------------------------------------------------------- e2e
METRICS_WORKER = """
    import os, time
    import numpy as np
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import horovod_tpu as hvd
    hvd.init()
    assert hvd.size() == 2
    for i in range(80):
        out = np.asarray(hvd.allreduce(
            np.ones(256, np.float32), name=f'metrics.step.{i}',
            op=hvd.Sum))
        assert out[0] == hvd.size()
        time.sleep(0.05)
    print('METRICS_WORKER_DONE', hvd.rank(), flush=True)
    hvd.shutdown()
"""


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def validate_exposition(text: str) -> None:
    """Assertions shared with tools/metrics_smoke.py: the scraped page is
    well-formed Prometheus text carrying per-op latency histograms from
    BOTH ranks, the RPC/KV counter families, and the driver's elastic
    gauges."""
    parsed = mexport.parse_prometheus(text)  # raises on malformed lines
    hist = parsed["hvd_op_execute_seconds"]
    assert hist["type"] == "histogram"
    counts = {
        labels.get("rank"): v
        for name, labels, v in hist["samples"]
        if name.endswith("_count") and labels.get("op") == "ALLREDUCE"
    }
    assert counts.get("0", 0) > 0 and counts.get("1", 0) > 0, counts
    # Cumulative bucket sanity on one series: +Inf equals the count.
    for rank in ("0", "1"):
        inf = [
            v for name, labels, v in hist["samples"]
            if name.endswith("_bucket") and labels.get("rank") == rank
            and labels.get("op") == "ALLREDUCE"
            and labels.get("le") == "+Inf"
        ]
        assert inf and inf[0] == counts[rank]
    assert parsed["hvd_op_negotiate_seconds"]["type"] == "histogram"
    # RPC retry counter family is always exposed (pre-seeded zeros).
    assert parsed["hvd_rpc_retries_total"]["type"] == "counter"
    # KV traffic from the pushers themselves shows up driver-side.
    assert any(
        v > 0 for _, _, v in parsed["hvd_kv_server_requests_total"]["samples"]
    )
    # Driver-role elastic gauges.
    world = {
        labels.get("role"): v
        for _, labels, v in parsed["hvd_elastic_world_size"]["samples"]
    }
    assert world.get("driver") == 2.0
    gens = parsed["hvd_elastic_generations_total"]["samples"]
    assert any(
        labels.get("role") == "driver" and v >= 1 for _, labels, v in gens
    )


def run_metrics_job(timeout=120):
    """Launch a 2-rank CPU-mesh job through the real elastic driver with
    HOROVOD_METRICS=1 and scrape GET /metrics off the driver's rendezvous
    server while it runs. Returns (exit_code, scraped_text, all_output).
    Shared with tools/metrics_smoke.py."""
    import tempfile

    port = _free_port()
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_CYCLE_TIME": "1",
            "HOROVOD_METRICS": "1",
            "HOROVOD_METRICS_PORT": str(port),
            "HOROVOD_METRICS_PUSH_INTERVAL_S": "0.25",
            "PYTHONPATH": os.pathsep.join(
                [REPO, env.get("PYTHONPATH", "")]
            ).rstrip(os.pathsep),
        }
    )
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(textwrap.dedent(METRICS_WORKER))
        proc = subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.run",
             "-np", "2", "--min-np", "2", "--max-np", "2",
             "--output-dir", td, sys.executable, script],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        url = f"http://127.0.0.1:{port}/metrics"
        good_text = None
        last_err = None
        deadline = time.monotonic() + timeout
        try:
            while time.monotonic() < deadline and proc.poll() is None:
                time.sleep(0.25)
                try:
                    with urllib.request.urlopen(url, timeout=5) as resp:
                        text = resp.read().decode()
                    validate_exposition(text)
                    good_text = text
                    break
                except Exception as exc:  # noqa: BLE001 - retry until the
                    last_err = exc       # pushers have reported
            out, _ = proc.communicate(
                timeout=max(5.0, deadline - time.monotonic())
            )
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
        text_out = out.decode(errors="replace")
        for fn in sorted(os.listdir(td)):
            if fn.startswith("worker.") and fn.endswith((".out", ".err")):
                with open(os.path.join(td, fn), errors="replace") as f:
                    text_out += f"\n--- {fn} ---\n" + f.read()
        if good_text is None:
            raise AssertionError(
                f"never scraped a valid exposition (last error: "
                f"{last_err!r}); job output:\n{text_out}"
            )
        return proc.returncode, good_text, text_out


def test_two_rank_metrics_scrape_e2e():
    """Acceptance: a 2-rank CPU-mesh run with HOROVOD_METRICS=1 serves
    Prometheus text on the driver's /metrics with per-op histograms from
    both ranks (rank labels), RPC counter families, and elastic gauges;
    the job itself completes cleanly."""
    rc, text, out = run_metrics_job()
    assert rc == 0, out
    assert "METRICS_WORKER_DONE 0" in out and "METRICS_WORKER_DONE 1" in out
    validate_exposition(text)
