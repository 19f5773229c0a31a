"""Direct tests of the native control-plane core (cpp/libhvd_core.so)
through the C ABI: plan emission, fusion grouping, ticket lifecycle,
duplicate rejection, autotune movement.
"""

import os
import time

import pytest

import horovod_tpu as hvd
from horovod_tpu.common.basics import NativeCore, _CoreError
from horovod_tpu.common.env import Config
from horovod_tpu.common.topology import Topology


SINGLE = Topology(rank=0, size=1, local_rank=0, local_size=1,
                  cross_rank=0, cross_size=1)


@pytest.fixture()
def core(monkeypatch):
    hvd.shutdown()  # the C++ core is a per-process singleton
    # Deterministic fusion for the grouping assertions: a generous
    # quiescence window (20 ms, bounded by a 50 ms cycle) so a loaded CI
    # host's enqueue gaps can't split one Python burst across cycles
    # (the production default seals a solo request after 100 us — that
    # latency optimization is exactly what would flake here).
    # monkeypatch restores/removes the var even if init raises.
    monkeypatch.setenv("HOROVOD_TPU_LINGER_US", "20000")
    c = NativeCore()
    cfg = Config()
    cfg.cycle_time_ms = 50.0
    c.init(cfg, SINGLE)
    yield c
    c.shutdown()


def _drain_plans(core, max_plans=10, timeout_ms=500):
    plans = []
    deadline = time.monotonic() + timeout_ms / 1000.0
    while time.monotonic() < deadline and len(plans) < max_plans:
        p = core.next_plan(timeout_ms=50)
        if isinstance(p, dict):
            plans.append(p)
            core.plan_done(p["id"], 0, "", 0.001, int(p.get("total_bytes", 0)))
        elif p == -1:
            break
    return plans


def test_fusion_groups_same_dtype(core):
    # 3 small f32 allreduces + 1 i32: expect 2 plans (f32 fused, i32 alone).
    for i in range(3):
        core.enqueue(0, f"t{i}", 7, [4, 4], -1, 2, 1.0, 1.0)
    core.enqueue(0, "t_int", 4, [8], -1, 2, 1.0, 1.0)
    plans = _drain_plans(core, max_plans=4)
    by_names = {tuple(sorted(p["names"])): p for p in plans}
    assert ("t0", "t1", "t2") in by_names, plans
    assert ("t_int",) in by_names, plans
    fused = by_names[("t0", "t1", "t2")]
    assert fused["total_bytes"] == 3 * 16 * 4
    assert fused["shapes"] == [[4, 4], [4, 4], [4, 4]]


def test_fusion_respects_threshold():
    hvd.shutdown()
    c = NativeCore()
    cfg = Config()
    cfg.cycle_time_ms = 1.0
    cfg.fusion_threshold_bytes = 100  # tiny: 2 x 16-float tensors don't fit
    c.init(cfg, SINGLE)
    try:
        c.enqueue(0, "a", 7, [16], -1, 2, 1.0, 1.0)
        c.enqueue(0, "b", 7, [16], -1, 2, 1.0, 1.0)
        plans = _drain_plans(c, max_plans=2)
        assert len(plans) == 2
        assert all(len(p["names"]) == 1 for p in plans)
    finally:
        c.shutdown()


def test_ticket_lifecycle(core):
    t = core.enqueue(0, "x", 7, [2], -1, 2, 1.0, 1.0)
    assert t > 0
    state, _ = core.ticket_status(t)
    # complete the plan
    plans = _drain_plans(core, max_plans=1)
    assert plans
    deadline = time.monotonic() + 2
    while time.monotonic() < deadline:
        state, err = core.ticket_status(t)
        if state != 0:
            break
        time.sleep(0.005)
    assert state == 1, (state, err)


def test_ticket_error_propagates(core):
    t = core.enqueue(0, "bad", 7, [2], -1, 2, 1.0, 1.0)
    p = None
    deadline = time.monotonic() + 2
    while time.monotonic() < deadline and not isinstance(p, dict):
        p = core.next_plan(timeout_ms=50)
    assert isinstance(p, dict)
    core.plan_done(p["id"], 1, "boom", 0.0, 0)
    deadline = time.monotonic() + 2
    state = 0
    while time.monotonic() < deadline:
        state, err = core.ticket_status(t)
        if state != 0:
            break
        time.sleep(0.005)
    assert state < 0
    assert "boom" in err


def test_duplicate_name_rejected_at_core(core):
    core.enqueue(0, "dup", 7, [2], -1, 2, 1.0, 1.0)
    with pytest.raises(_CoreError):
        core.enqueue(0, "dup", 7, [2], -1, 2, 1.0, 1.0)
    _drain_plans(core, max_plans=1)


def test_broadcast_not_fused(core):
    core.enqueue(2, "b0", 7, [4], 0, 2, 1.0, 1.0)
    core.enqueue(2, "b1", 7, [4], 0, 2, 1.0, 1.0)
    plans = _drain_plans(core, max_plans=2)
    assert len(plans) == 2
    assert all(p["type"] == 2 and p["root"] == 0 for p in plans)


def test_autotune_moves_params():
    hvd.shutdown()
    c = NativeCore()
    cfg = Config()
    cfg.cycle_time_ms = 1.0
    cfg.autotune = True
    cfg.autotune_warmup_samples = 0
    cfg.autotune_steps_per_sample = 1
    c.init(cfg, SINGLE)
    try:
        initial = (c.cycle_time_ms(), c.fusion_threshold())
        changed = False
        for i in range(40):
            c.enqueue(0, f"at{i}", 7, [1024], -1, 2, 1.0, 1.0)
            deadline = time.monotonic() + 2
            p = None
            while time.monotonic() < deadline and not isinstance(p, dict):
                p = c.next_plan(timeout_ms=50)
            assert isinstance(p, dict)
            c.plan_done(p["id"], 0, "", 0.001, 4096)
            if (c.cycle_time_ms(), c.fusion_threshold()) != initial:
                changed = True
                break
        assert changed, "autotuner never proposed new parameters"
    finally:
        c.shutdown()


def test_join_plan_roundtrip(core):
    t = core.enqueue_join()
    p = None
    deadline = time.monotonic() + 2
    while time.monotonic() < deadline and not isinstance(p, dict):
        p = core.next_plan(timeout_ms=50)
    assert isinstance(p, dict) and p["type"] == 3
    core.plan_done(p["id"], 0, "", 0.0, 0)
    deadline = time.monotonic() + 2
    state = 0
    while time.monotonic() < deadline:
        state, _ = core.ticket_status(t)
        if state != 0:
            break
        time.sleep(0.005)
    assert state == 1


def test_response_cache_roundtrip(core):
    """Second submission of the same signature rides the cache-bit path and
    still completes with a correct plan."""
    core.enqueue(0, "cached", 7, [8], -1, 2, 1.0, 1.0)
    plans = _drain_plans(core, max_plans=1)
    assert plans and core.cache_size() >= 1
    # same name+shape+op again: travels as a cache bit this time
    t = core.enqueue(0, "cached", 7, [8], -1, 2, 1.0, 1.0)
    plans = _drain_plans(core, max_plans=1)
    assert plans and plans[0]["names"] == ["cached"]
    assert plans[0]["shapes"] == [[8]]
    deadline = time.monotonic() + 2
    state = 0
    while time.monotonic() < deadline:
        state, _ = core.ticket_status(t)
        if state != 0:
            break
        time.sleep(0.005)
    assert state == 1


def test_autotune_categorical_flags_in_plans_and_convergence():
    """The tuner explores the categorical dims (cache always; hierarchical
    needs a grid) and the verdict stamps every plan with tuned_flags
    (reference jointly tunes hierarchical_allreduce/hierarchical_allgather/
    cache_enabled, parameter_manager.h:42-246). After the sample budget the
    tuner freezes and the pinned flags keep flowing."""
    hvd.shutdown()
    c = NativeCore()
    cfg = Config()
    cfg.cycle_time_ms = 1.0
    cfg.autotune = True
    cfg.autotune_warmup_samples = 0
    cfg.autotune_steps_per_sample = 1
    c.init(cfg, SINGLE)
    try:
        seen_flags = set()
        # 24 GP samples x 5 scores/median = 120 plans to convergence.
        for i in range(140):
            c.enqueue(0, f"cat{i}", 7, [256], -1, 2, 1.0, 1.0)
            deadline = time.monotonic() + 2
            p = None
            while time.monotonic() < deadline and not isinstance(p, dict):
                p = c.next_plan(timeout_ms=50)
            assert isinstance(p, dict)
            assert p["tuned_flags"] >= 0, p  # autotune on => flags stamped
            seen_flags.add(p["tuned_flags"])
            c.plan_done(p["id"], 0, "", 0.001, 1024)
        # cache dim explored: both cache-on and cache-off must have been
        # proposed at least once across the sweep.
        assert len(seen_flags) > 1, seen_flags
        final = c.tuned_flags()
        # Converged: flags stable from here on.
        for i in range(5):
            c.enqueue(0, f"post{i}", 7, [256], -1, 2, 1.0, 1.0)
            deadline = time.monotonic() + 2
            p = None
            while time.monotonic() < deadline and not isinstance(p, dict):
                p = c.next_plan(timeout_ms=50)
            assert isinstance(p, dict)
            assert p["tuned_flags"] == final, (p, final)
            c.plan_done(p["id"], 0, "", 0.001, 1024)
    finally:
        c.shutdown()


def test_eager_wakeup_beats_cycle_cadence():
    """Event-driven wakeup (TPU-build improvement over the reference's
    fixed RunLoopOnce cadence): with a deliberately huge cycle time, an
    enqueued tensor must still produce a plan almost immediately when
    wakeup is on, and only at the cycle boundary when forced off."""
    hvd.shutdown()

    def time_to_plan(env):
        for k, v in env.items():
            os.environ[k] = v
        try:
            c = NativeCore()
            cfg = Config()
            cfg.cycle_time_ms = 1000.0
            c.init(cfg, SINGLE)
            try:
                t0 = time.monotonic()
                c.enqueue(0, "wake", 7, [4], -1, 2, 1.0, 1.0)
                deadline = time.monotonic() + 3
                p = None
                while time.monotonic() < deadline and not isinstance(p, dict):
                    p = c.next_plan(timeout_ms=50)
                assert isinstance(p, dict)
                dt = time.monotonic() - t0
                c.plan_done(p["id"], 0, "", 0.001, 16)
                return dt
            finally:
                c.shutdown()
        finally:
            for k in env:
                os.environ.pop(k, None)

    fast = time_to_plan({})  # wakeup defaults on
    slow = time_to_plan({"HOROVOD_TPU_EAGER_WAKEUP": "0"})
    # Absolute bounds relaxed for the shared-core CI host (a full-suite
    # run can preempt this process for hundreds of ms); the relative
    # separation is the real claim.
    assert fast < 0.8, f"eager wakeup did not fire: {fast:.3f}s"
    # The cadence path fires at the ~1.0s cycle boundary, so the relative
    # bound must stay below that: demand clear separation, not a multiple
    # of a possibly-preempted `fast`.
    assert slow > 0.8 and slow > fast + 0.2, (
        f"cadence path returned too early: {slow:.3f}s (fast {fast:.3f}s)"
    )


def test_start_timeout_bounds_rendezvous():
    """A worker that never launches must abort rank 0 at
    HOROVOD_START_TIMEOUT (reference --start-timeout), not hang accept()
    forever."""
    hvd.shutdown()
    os.environ["HOROVOD_START_TIMEOUT"] = "3"
    try:
        topo = Topology(rank=0, size=2, local_rank=0, local_size=2,
                        cross_rank=0, cross_size=1)
        c = NativeCore()
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="timed out"):
            c.init(Config(), topo, coord_addr="127.0.0.1",
                   coord_port=29437)
        assert time.monotonic() - t0 < 30
    finally:
        os.environ.pop("HOROVOD_START_TIMEOUT", None)


def test_grouped_requests_hold_until_complete(core):
    # First-class group: members enqueued across different cycles still
    # emit as ONE plan once the last member lands (the coordinator holds
    # the group; cycle boundaries are irrelevant).
    gid = 77
    core.enqueue(0, "g.0", 7, [4], -1, 2, 1.0, 1.0, gid, 3)
    # Let several 1 ms cycles pass: the lone member must NOT emit.
    assert _drain_plans(core, max_plans=1, timeout_ms=120) == []
    core.enqueue(0, "g.1", 7, [4], -1, 2, 1.0, 1.0, gid, 3)
    assert _drain_plans(core, max_plans=1, timeout_ms=120) == []
    core.enqueue(0, "g.2", 7, [4], -1, 2, 1.0, 1.0, gid, 3)
    plans = _drain_plans(core, max_plans=2, timeout_ms=500)
    assert len(plans) == 1, plans
    assert sorted(plans[0]["names"]) == ["g.0", "g.1", "g.2"], plans


def test_grouped_fusion_exempt_from_threshold(core):
    # A group larger than the fusion threshold still fuses into one plan
    # (the group explicitly requested one collective).
    import horovod_tpu.common.basics as basics

    gid = 88
    # 3 x 1 MB f32 with a tiny threshold would normally split; grouped
    # must not. (Threshold is a Config field read at init; default is
    # 64 MB, so make the members bigger than a forced-small threshold by
    # re-initing the core with fusion_threshold=16 bytes.)
    core.shutdown()
    c = basics.NativeCore()
    cfg = Config()
    cfg.cycle_time_ms = 1.0
    cfg.fusion_threshold = 16
    c.init(cfg, SINGLE)
    try:
        for i in range(3):
            c.enqueue(0, f"big.{i}", 7, [64], -1, 2, 1.0, 1.0, gid, 3)
        plans = _drain_plans(c, max_plans=3, timeout_ms=500)
        assert len(plans) == 1, plans
        assert len(plans[0]["names"]) == 3, plans
    finally:
        c.shutdown()


def test_grouped_heterogeneous_dtypes_split_counted(core):
    # Mixed-dtype group: one plan per signature, and the split is counted.
    gid = 99
    before = core.grouped_splits()
    core.enqueue(0, "mix.0", 7, [4], -1, 2, 1.0, 1.0, gid, 2)  # f32
    core.enqueue(0, "mix.1", 4, [4], -1, 2, 1.0, 1.0, gid, 2)  # i32
    plans = _drain_plans(core, max_plans=3, timeout_ms=500)
    assert len(plans) == 2, plans
    assert core.grouped_splits() == before + 1


def test_runtime_timeline_start_stop(tmp_path):
    """hvd.start_timeline / stop_timeline (later-reference API): the
    catapult trace can be scoped to a window at runtime."""
    import json

    hvd.shutdown()
    hvd.init()
    try:
        path = str(tmp_path / "tl.json")
        hvd.start_timeline(path, mark_cycles=True)
        with pytest.raises(ValueError):
            hvd.start_timeline(path)        # already active
        import numpy as np

        hvd.allreduce(np.ones((4,), np.float32), name="tl.t")
        hvd.stop_timeline()
        events = json.load(open(path))
        names = {e.get("name") for e in events}
        assert any("XLA_" in str(n) or "ENQUEUE" in str(n) for n in names), names
        assert "CYCLE" in names, names
        # restartable after stop
        path2 = str(tmp_path / "tl2.json")
        hvd.start_timeline(path2, mark_cycles=False)
        hvd.allreduce(np.ones((2,), np.float32), name="tl.t2")
        hvd.stop_timeline()
        events2 = json.load(open(path2))
        assert all(e.get("name") != "CYCLE" for e in events2), events2
    finally:
        hvd.shutdown()


def test_init_raises_when_the_core_cannot_be_built(monkeypatch):
    """No quiet second try: a native core that cannot be built from
    cpp/src fails ``hvd.init()``; the Python runtime is only ever chosen
    by ``HOROVOD_TPU_CORE=python``."""
    from horovod_tpu.common import basics

    hvd.shutdown()
    monkeypatch.delenv("HOROVOD_TPU_CORE", raising=False)
    monkeypatch.setattr(basics, "_lib", None)

    def no_compiler():
        raise basics.NativeCoreUnavailable("failed to build native core")

    monkeypatch.setattr(basics, "ensure_built", no_compiler)
    with pytest.raises(basics.NativeCoreUnavailable):
        hvd.init()
    assert not hvd.is_initialized()
    monkeypatch.setenv("HOROVOD_TPU_CORE", "python")
    hvd.init()
    try:
        assert type(hvd._rt()).__name__ == "Runtime"
    finally:
        hvd.shutdown()


def test_shipped_library_loads_without_sources(tmp_path, monkeypatch):
    """An installed package holds cpp/libhvd_core.so and neither Makefile
    nor sources (setup.py): the library is loaded as it is, ``make`` is
    not called and nothing is written beside it."""
    import ctypes
    import shutil
    import subprocess

    from horovod_tpu.common import basics

    shipped = tmp_path / "cpp"
    shipped.mkdir()
    shutil.copy(basics.ensure_built(), shipped / "libhvd_core.so")
    monkeypatch.setattr(basics, "_CPP_DIR", str(shipped))
    monkeypatch.setattr(basics, "_LIB_PATH",
                        str(shipped / "libhvd_core.so"))

    def no_make(*a, **k):
        raise AssertionError(f"make called: {a}")

    monkeypatch.setattr(subprocess, "run", no_make)
    path = basics.ensure_built()
    assert path == str(shipped / "libhvd_core.so")
    assert ctypes.CDLL(path).hvd_core_initialized() == 0
    assert os.listdir(shipped) == ["libhvd_core.so"]
    os.remove(path)
    with pytest.raises(basics.NativeCoreUnavailable, match="no sources"):
        basics.ensure_built()


def test_core_rebuilds_only_when_a_source_is_newer(tmp_path, monkeypatch):
    """With the sources present, ``make`` runs when a source or header is
    newer than the library or the library is missing, and not otherwise
    (a current checkout is not written to)."""
    import subprocess

    from horovod_tpu.common import basics

    cpp = tmp_path / "cpp"
    (cpp / "src").mkdir(parents=True)
    (cpp / "include" / "hvd").mkdir(parents=True)
    for rel in ("Makefile", "src/core.cc", "include/hvd/core.h"):
        (cpp / rel).write_text("")
        os.utime(cpp / rel, (1000, 1000))
    lib = cpp / "libhvd_core.so"
    monkeypatch.setattr(basics, "_CPP_DIR", str(cpp))
    monkeypatch.setattr(basics, "_LIB_PATH", str(lib))
    calls = []
    monkeypatch.setattr(subprocess, "run",
                        lambda cmd, **k: calls.append(cmd))

    basics.ensure_built()
    assert len(calls) == 1 and calls[0][0] == "make", calls
    lib.write_text("")
    os.utime(lib, (2000, 2000))
    basics.ensure_built()
    assert len(calls) == 1, "current library rebuilt"
    for i, rel in enumerate(("src/core.cc", "include/hvd/core.h",
                             "Makefile")):
        os.utime(cpp / rel, (3000, 3000))
        basics.ensure_built()
        assert len(calls) == 2 + i, (rel, calls)
        os.utime(cpp / rel, (1000, 1000))
