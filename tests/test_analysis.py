"""Collective-safety static analyzer tests (horovod_tpu/analysis/).

Covers the acceptance matrix of the analyzer: clean jaxpr → no findings;
each seeded defect class (unknown mesh axis, dtype-mismatched grouped
allreduce, non-bijective ppermute, cross-rank ordering divergence,
lock-discipline violation) is detected; suppression comments work; the
CLI reports zero findings on the shipped examples and stays within its
time budget.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import analysis
from horovod_tpu.analysis import preflight
from horovod_tpu.analysis.findings import (
    RULE_GROUP_BUDGET,
    RULE_GROUP_DTYPE,
    RULE_MISSING_COLLECTIVE,
    RULE_ORDER_MISMATCH,
    RULE_PPERMUTE,
    RULE_SIGNATURE_MISMATCH,
    RULE_UNGUARDED,
    RULE_UNKNOWN_AXIS,
)
from horovod_tpu.jax import _shard_map
from horovod_tpu.parallel.mesh import build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh():
    return build_mesh({"data": len(jax.devices())})


def _wrap(body, mesh, n_in=1, out_spec=P()):
    return _shard_map(
        body, mesh, in_specs=(P("data"),) * n_in, out_specs=out_spec
    )


# ---------------------------------------------------------------------------
# Pass 1: jaxpr lint
# ---------------------------------------------------------------------------

def test_clean_jaxpr_no_findings():
    mesh = _mesh()
    fn = _wrap(lambda x: lax.psum(x, "data"), mesh)
    assert analysis.lint_step(fn, jnp.ones((8, 4)), mesh=mesh) == []


def test_clean_train_step_no_findings():
    """The full compiled-mode pipeline (fused allreduce inside a jitted
    train step) lints clean."""
    import optax

    import horovod_tpu.jax as hvdj

    mesh = _mesh()

    def loss_fn(p, batch):
        return jnp.mean((batch @ p) ** 2)

    tx = hvdj.DistributedOptimizer(optax.sgd(0.01))
    step = hvdj.make_train_step(loss_fn, tx, mesh, donate=False)
    params = jnp.ones((4, 2))
    opt_state = tx.init(params)
    batch = jnp.ones((8, 4))
    findings = analysis.lint_step(
        step, params, opt_state, batch, mesh=mesh,
        fusion_threshold_bytes=64 * 1024 * 1024,
    )
    assert findings == []


def test_unknown_mesh_axis():
    mesh = _mesh()
    fn = _wrap(lambda x: lax.psum(x, "data"), mesh)
    findings = analysis.lint_step(
        fn, jnp.ones((8, 4)), mesh={"model": 8}
    )
    assert [f.rule for f in findings] == [RULE_UNKNOWN_AXIS]
    assert "'data'" in findings[0].message
    assert findings[0].severity == "error"


def test_unknown_axis_at_trace_time():
    """An axis jax itself rejects at trace time (unbound name) is
    reported as an unknown-axis finding, not an exception."""
    findings = analysis.lint_step(
        lambda x: lax.psum(x, "nonexistent"), jnp.ones(4)
    )
    assert [f.rule for f in findings] == [RULE_UNKNOWN_AXIS]


def test_nested_scan_pjit_collectives_are_found():
    mesh = _mesh()

    def body(x):
        def inner(carry, _):
            return carry + lax.psum(x, "data"), None

        out, _ = lax.scan(inner, x, None, length=2)
        return jax.jit(lambda t: lax.psum(t, "data"))(out)

    fn = _wrap(body, mesh)
    jx = jax.make_jaxpr(fn)(jnp.ones((8, 4)))
    sites = analysis.collect_collectives(jx)
    assert len(sites) == 2
    assert {"scan" in s.path or "jit" in s.path for s in sites} == {True}


def test_non_bijective_ppermute_hole():
    mesh = _mesh()
    n = len(jax.devices())
    # Ring missing its last link: rank 0 never receives.
    perm = [(i, i + 1) for i in range(n - 1)]
    fn = _wrap(
        lambda x: lax.ppermute(x, "data", perm), mesh, out_spec=P("data")
    )
    findings = analysis.lint_step(fn, jnp.ones((8, 4)))
    assert [f.rule for f in findings] == [RULE_PPERMUTE]
    assert "never receive" in findings[0].message


def test_masked_partial_ppermute_is_clean():
    """The guarded-partial-permute idiom (result consumed only through
    jnp.where) — the in-repo binomial broadcast — must NOT be flagged."""
    from horovod_tpu.ops.collectives import broadcast

    mesh = _mesh()
    fn = _wrap(
        lambda x: broadcast(x, root_rank=0, axis_name="data"),
        mesh, out_spec=P("data"),
    )
    assert analysis.lint_step(fn, jnp.ones((8, 4))) == []


def test_complete_ring_ppermute_is_clean():
    mesh = _mesh()
    n = len(jax.devices())
    perm = [(i, (i + 1) % n) for i in range(n)]
    fn = _wrap(
        lambda x: lax.ppermute(x, "data", perm), mesh, out_spec=P("data")
    )
    assert analysis.lint_step(fn, jnp.ones((8, 4))) == []


# ---------------------------------------------------------------------------
# Pass 1: grouped-allreduce checks
# ---------------------------------------------------------------------------

def test_group_dtype_mismatch():
    tensors = [
        np.ones(4, np.float32),
        np.ones(4, np.float16),
    ]
    findings = analysis.check_group(tensors, name="mixed")
    assert [f.rule for f in findings] == [RULE_GROUP_DTYPE]
    assert "float16" in findings[0].message
    assert "float32" in findings[0].message


def test_group_over_budget():
    tensors = [np.ones(1024, np.float32)] * 2  # 8 KiB total
    findings = analysis.check_group(
        tensors, threshold_bytes=4096, name="big"
    )
    assert [f.rule for f in findings] == [RULE_GROUP_BUDGET]
    assert findings[0].details["total_bytes"] == 8192


def test_clean_group():
    tensors = [np.ones(8, np.float32)] * 3
    assert analysis.check_group(
        tensors, threshold_bytes=1 << 20, name="ok"
    ) == []


def test_grouped_allreduce_preflight_raises(hvd_session, monkeypatch):
    """With HOROVOD_TPU_STATIC_CHECKS on, a dtype-mixed group is rejected
    before any member is enqueued."""
    monkeypatch.setattr(preflight, "_enabled_cache", True)
    try:
        with pytest.raises(analysis.CollectiveSafetyError) as exc:
            hvd_session.grouped_allreduce(
                [np.ones(4, np.float32), np.ones(4, np.float16)],
                name="pf.mixed",
            )
        assert RULE_GROUP_DTYPE in str(exc.value)
    finally:
        preflight._reset_for_tests(None)


def test_allreduce_gradients_preflight_unbound_axis(monkeypatch):
    """Compiled-mode pre-flight: reducing over an unbound axis raises a
    CollectiveSafetyError at trace time (instead of jax's NameError deep
    inside the fusion pass)."""
    import horovod_tpu.jax as hvdj

    monkeypatch.setattr(preflight, "_enabled_cache", True)
    try:
        with pytest.raises(analysis.CollectiveSafetyError):
            jax.make_jaxpr(
                lambda g: hvdj.allreduce_gradients(g, axis_name="data")
            )(jnp.ones(4))
    finally:
        preflight._reset_for_tests(None)


# ---------------------------------------------------------------------------
# Pass 1: cross-rank ordering
# ---------------------------------------------------------------------------

def _trace(*entries):
    return [
        analysis.CollectiveCall(
            op=e[0], name=e[1],
            process_set_id=e[2] if len(e) > 2 else 0,
            dtype="float32", shape=(4,),
        )
        for e in entries
    ]


def test_order_mismatch_names_tensors_and_ranks():
    traces = {
        0: _trace(("allreduce", "grad.w"), ("allreduce", "grad.b")),
        1: _trace(("allreduce", "grad.b"), ("allreduce", "grad.w")),
    }
    findings = analysis.check_cross_rank_order(traces)
    assert [f.rule for f in findings] == [RULE_ORDER_MISMATCH]
    msg = findings[0].message
    assert "grad.w" in msg and "grad.b" in msg
    assert "rank 0" in msg and "rank 1" in msg


def test_missing_collective_detected():
    traces = {
        0: _trace(("allreduce", "a"), ("allreduce", "b")),
        1: _trace(("allreduce", "a")),
    }
    findings = analysis.check_cross_rank_order(traces)
    assert [f.rule for f in findings] == [RULE_MISSING_COLLECTIVE]
    assert "'b'" in findings[0].message


def test_signature_mismatch_detected():
    traces = {
        0: [analysis.CollectiveCall("allreduce", "g", 0, "float32", (4,))],
        1: [analysis.CollectiveCall("allreduce", "g", 0, "float32", (8,))],
    }
    findings = analysis.check_cross_rank_order(traces)
    assert [f.rule for f in findings] == [RULE_SIGNATURE_MISMATCH]


def test_order_checked_per_process_set():
    """Different sets are independent streams: interleaving differences
    ACROSS sets are legal; only within-set divergence is flagged."""
    traces = {
        0: _trace(("allreduce", "a", 1), ("allreduce", "x", 2)),
        1: _trace(("allreduce", "x", 2), ("allreduce", "a", 1)),
    }
    assert analysis.check_cross_rank_order(traces) == []


def test_simulated_rank_traces_use_name_registry():
    """record_rank_trace runs real hvd.* calls against the recording
    runtime; auto-generated names come from the tensor-name registry and
    line up across simulated ranks."""

    def fn():
        hvd.allreduce(np.ones(4, np.float32))  # auto name
        hvd.allgather(np.ones(2, np.float32), name="ag.x")

    traces = analysis.simulate_ranks(fn, 4)
    assert len(traces) == 4
    for r in range(4):
        assert [c.name for c in traces[r]] == [
            "allreduce.noname.0", "ag.x"
        ]
    assert analysis.check_cross_rank_order(traces) == []


def test_simulated_divergent_orders_flagged():
    def fn():
        a = np.ones(4, np.float32)
        if hvd.rank() == 1:
            hvd.allreduce(a, name="second")
            hvd.allreduce(a, name="first")
        else:
            hvd.allreduce(a, name="first")
            hvd.allreduce(a, name="second")

    traces = analysis.simulate_ranks(fn, 2)
    findings = analysis.check_cross_rank_order(traces)
    assert [f.rule for f in findings] == [RULE_ORDER_MISMATCH]


# ---------------------------------------------------------------------------
# Pass 2: runtime thread-safety lint
# ---------------------------------------------------------------------------

_FIXTURE_RULES = {
    "Worker": analysis.ClassRule(
        attrs={
            "_table": analysis.AttrRule("_lock"),
            "_loop_state": analysis.AttrRule(
                None, confined_to=("run_loop",)
            ),
        },
        lock_aliases={"_cv": "_lock"},
    ),
}


def test_lock_discipline_violation_fixture():
    src = textwrap.dedent(
        """
        class Worker:
            def __init__(self):
                self._table = {}
                self._loop_state = 0

            def good(self, k, v):
                with self._lock:
                    self._table[k] = v

            def good_via_cv(self, k):
                with self._cv:
                    self._table.pop(k, None)

            def bad(self, k, v):
                self._table[k] = v

            def bad_mutator(self):
                self._table.clear()

            def run_loop(self):
                self._loop_state += 1

            def bad_confined(self):
                self._loop_state = 7
        """
    )
    findings = analysis.lint_source(src, _FIXTURE_RULES, "fixture.py")
    assert [f.rule for f in findings] == [RULE_UNGUARDED] * 3
    methods = {f.details["method"] for f in findings}
    assert methods == {"bad", "bad_mutator", "bad_confined"}


def test_lock_discipline_suppression_comment():
    src = textwrap.dedent(
        """
        class Worker:
            def bad_but_known(self, k, v):
                self._table[k] = v  # hvd-analysis: ignore[unguarded-shared-state]

            def bad_above(self, k, v):
                # hvd-analysis: ignore
                self._table[k] = v

            def still_bad(self, k, v):
                self._table[k] = v  # hvd-analysis: ignore[some-other-rule]
        """
    )
    findings = analysis.lint_source(src, _FIXTURE_RULES, "fixture.py")
    assert len(findings) == 1
    assert findings[0].details["method"] == "still_bad"


def test_nested_function_does_not_inherit_lock():
    """A closure defined under a lock runs later on another thread: the
    lock held at definition time must not count."""
    src = textwrap.dedent(
        """
        class Worker:
            def sneaky(self, k, v):
                with self._lock:
                    def later():
                        self._table[k] = v
                    return later
        """
    )
    findings = analysis.lint_source(src, _FIXTURE_RULES, "fixture.py")
    assert len(findings) == 1


def test_runtime_sources_are_clean():
    """Regression for the analyzer-driven fixes: the shipped runtime
    sources satisfy their declared lock discipline (Runtime._process_sets
    and Runtime.joined were unguarded in the seed)."""
    assert analysis.lint_runtime() == []


def test_runtime_discipline_covers_fixed_attributes():
    rules = analysis.DEFAULT_DISCIPLINE["core/runtime.py"]["Runtime"]
    assert rules.attrs["_process_sets"].lock == "_state_lock"
    assert rules.attrs["joined"].lock == "_state_lock"


def test_module_level_discipline_covers_new_packages():
    """PR 8: the lock-discipline pass extends to the packages added
    since PR 1 — module-global tap state and the metrics registry."""
    disc = analysis.DEFAULT_DISCIPLINE
    assert disc["fault/injector.py"][analysis.MODULE].attrs[
        "_seq"].lock == "_lock"
    assert disc["guard/__init__.py"][analysis.MODULE].attrs[
        "TAP"].lock == "_lock"
    assert disc["metrics/registry.py"]["Registry"].attrs[
        "_metrics"].lock == "_lock"
    assert "run/journal.py" in disc
    # The topo planning layer is declared stateless (empty discipline).
    assert disc["topo/compositor.py"] == {}


def test_module_level_lint_flags_unguarded_global():
    src = textwrap.dedent(
        """
        import threading
        _lock = threading.Lock()
        _table = {}
        ACTIVE = False

        def good(v):
            global ACTIVE
            with _lock:
                _table["k"] = v
                ACTIVE = True

        def bad(v):
            global ACTIVE
            _table["k"] = v
            ACTIVE = True

        def local_shadow():
            ACTIVE = True  # local binding, not the module global
            return ACTIVE

        def bad_mutator():
            _table.clear()
        """
    )
    rules = {analysis.MODULE: analysis.ClassRule(attrs={
        "_table": analysis.AttrRule("_lock"),
        "ACTIVE": analysis.AttrRule("_lock"),
    })}
    findings = analysis.lint_source(src, rules, "module_fixture.py")
    flagged = {(f.details["method"], f.details["attribute"])
               for f in findings}
    assert flagged == {
        ("bad", "_table"), ("bad", "ACTIVE"), ("bad_mutator", "_table"),
    }


def test_module_level_nested_def_does_not_inherit_lock():
    src = textwrap.dedent(
        """
        def sneaky():
            with _lock:
                def later():
                    _table.clear()
                return later
        """
    )
    rules = {analysis.MODULE: analysis.ClassRule(attrs={
        "_table": analysis.AttrRule("_lock"),
    })}
    findings = analysis.lint_source(src, rules, "module_fixture.py")
    assert [f.rule for f in findings] == [RULE_UNGUARDED]


def test_fault_injector_event_log_order_under_contention(tmp_path):
    """Regression for the race the extended pass surfaced: the event-log
    file append used to run OUTSIDE the injector lock, so two threads
    could invert this rank's (rank, seq) subsequence in the shared log —
    the byte-determinism chaos runs diff. Hammer record_event from many
    threads and assert the file's seq column is strictly increasing."""
    import threading

    from horovod_tpu.fault import injector
    from horovod_tpu.fault.plan import FaultPlan

    log = tmp_path / "events.jsonl"
    injector.install_plan(FaultPlan(seed=1, actions=[]))
    old = os.environ.get(injector.FAULT_EVENT_LOG_ENV)
    os.environ[injector.FAULT_EVENT_LOG_ENV] = str(log)
    try:
        n_threads, n_events = 8, 40

        def hammer(t):
            for i in range(n_events):
                injector.record_event("test-site", i + 1, "noop", f"t{t}")

        threads = [
            threading.Thread(target=hammer, args=(t,))
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        if old is None:
            os.environ.pop(injector.FAULT_EVENT_LOG_ENV, None)
        else:
            os.environ[injector.FAULT_EVENT_LOG_ENV] = old
        injector.reset()
    seqs = [
        json.loads(line)["seq"]
        for line in log.read_text().splitlines() if line
    ]
    assert len(seqs) == n_threads * n_events
    assert seqs == sorted(seqs), "event-log seq order inverted"
    assert len(set(seqs)) == len(seqs)


def _python_runtime():
    """A started pure-Python Runtime (the class the analyzer fixes
    target; the session fixture may pick the native C++ core instead)."""
    from horovod_tpu.common.env import Config
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.core.runtime import Runtime

    topo = Topology(
        rank=0, size=1, local_rank=0, local_size=1,
        cross_rank=0, cross_size=1,
    )
    rt = Runtime(Config(), topo)
    rt.start()
    return rt


def test_process_set_registration_is_thread_safe():
    """Regression (analyzer finding #1): concurrent register/remove from
    many threads while enqueues read membership must not corrupt the
    table or raise spuriously."""
    import threading

    rt = _python_runtime()
    errors = []

    def worker(base):
        try:
            for i in range(50):
                psid = base * 1000 + i + 1
                rt.register_process_set(psid, [0])
                assert rt._process_sets[psid] == [0]
                rt.remove_process_set(psid)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert not errors
        with rt._state_lock:
            assert rt._process_sets == {}
    finally:
        rt.shutdown()


def test_join_flag_guarded():
    """Regression (analyzer finding #2): join sets/clears the joined flag
    under the state lock; a join round-trip leaves it False."""
    rt = _python_runtime()
    try:
        rt.synchronize(rt.enqueue_join(), timeout=10.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with rt._state_lock:
                if not rt.joined:
                    break
            time.sleep(0.01)
        with rt._state_lock:
            assert rt.joined is False
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# CLI + JSON stability
# ---------------------------------------------------------------------------

def test_findings_json_is_stable():
    f1 = analysis.Finding(
        rule="b-rule", severity="warning", message="w", location="z",
        details={"k2": 1, "k1": 2},
    )
    f2 = analysis.Finding(
        rule="a-rule", severity="error", message="e", location="a",
    )
    doc = json.loads(analysis.findings_to_json([f1, f2]))
    assert [x["rule"] for x in doc["findings"]] == ["a-rule", "b-rule"]
    assert list(doc["findings"][0].keys()) == [
        "rule", "severity", "location", "message", "details"
    ]
    assert list(doc["findings"][1]["details"].keys()) == ["k1", "k2"]
    assert doc["summary"] == {"total": 2, "errors": 1, "warnings": 1}


def test_cli_clean_on_shipped_code():
    """Acceptance: zero findings on the shipped examples + runtime +
    plan grid + divergence variants + sharding table, exit 0, JSON shape
    stable and versioned, under the 60s CPU budget."""
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "collective_lint.py"),
         "--json", "all"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    elapsed = time.monotonic() - start
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["summary"]["total"] == 0
    assert doc["target"] == "all"
    assert doc["schema_version"] == 2
    assert doc["passes"] == [
        "divergence", "examples", "plans", "runtime", "sharding"
    ]
    assert doc["plans_verified"] > 100
    assert elapsed < 60, f"lint took {elapsed:.1f}s (budget 60s)"


def test_cli_json_stable_across_runs():
    """The versioned JSON document is byte-identical across two runs of
    the pure-python passes (the CI-diffing contract)."""
    cmd = [sys.executable, os.path.join(REPO, "tools",
                                        "collective_lint.py"),
           "--json", "plans"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    o1 = subprocess.run(cmd, capture_output=True, cwd=REPO, env=env,
                        timeout=120)
    o2 = subprocess.run(cmd, capture_output=True, cwd=REPO, env=env,
                        timeout=120)
    assert o1.returncode == 0
    assert o1.stdout == o2.stdout


def test_cli_exit_codes_distinguish_crash_from_findings():
    """Exit 2 = analyzer crash (bad usage / internal error), distinct
    from exit 1 = findings and exit 0 = clean."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "collective_lint.py"),
         "no-such-target"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 2


def test_cli_nonzero_exit_on_findings(tmp_path):
    """Seed a lock-discipline defect into a copy of runtime.py and point
    the Pass-2 lint at it through the API the CLI uses."""
    bad = tmp_path / "runtime.py"
    bad.write_text(textwrap.dedent(
        """
        class TensorQueue:
            def add(self, k, v):
                self._table[k] = v
        """
    ))
    findings = analysis.lint_runtime([str(bad)])
    assert [f.rule for f in findings] == [RULE_UNGUARDED]


# ---------------------------------------------------------------------------
# Call-site suppressions (PR 8)
# ---------------------------------------------------------------------------

def test_suppress_kwarg_filters_jaxpr_findings():
    mesh = _mesh()
    fn = _wrap(lambda x: lax.psum(x, "data"), mesh)
    args = (jnp.ones((8, 4)),)
    assert analysis.lint_step(fn, *args, mesh={"model": 8})
    assert analysis.lint_step(
        fn, *args, mesh={"model": 8}, suppress=["unknown-axis"]
    ) == []
    # A non-matching location glob keeps the finding.
    assert analysis.lint_step(
        fn, *args, mesh={"model": 8},
        suppress=["unknown-axis@*elsewhere*"],
    )
    # A matching one removes it (locations are jaxpr:<path>/<prim>).
    assert analysis.lint_step(
        fn, *args, mesh={"model": 8},
        suppress=["unknown-axis@jaxpr:*psum*"],
    ) == []


def test_suppressions_context_manager_is_scoped():
    mesh = _mesh()
    fn = _wrap(lambda x: lax.psum(x, "data"), mesh)
    args = (jnp.ones((8, 4)),)
    with analysis.suppressions("unknown-axis"):
        assert analysis.lint_step(fn, *args, mesh={"model": 8}) == []
        with analysis.suppressions("some-other-rule"):
            # Nesting adds, never replaces.
            assert analysis.lint_step(fn, *args, mesh={"model": 8}) == []
    # Out of scope: the finding is back.
    assert analysis.lint_step(fn, *args, mesh={"model": 8})


def test_suppressions_apply_to_divergence_findings():
    mesh = _mesh()

    def divergent(x):
        r = lax.axis_index("data")
        return lax.cond(
            r == 0, lambda v: lax.psum(v, "data"), lambda v: v, x
        )

    fn = _wrap(divergent, mesh, out_spec=P("data"))
    args = (jnp.ones((8, 4)),)
    assert analysis.analyze_step(fn, *args)
    assert analysis.analyze_step(
        fn, *args, suppress=["rank-divergent-collective"]
    ) == []
    with analysis.suppressions("rank-divergent-collective"):
        assert analysis.lint_step(fn, *args, mesh=_mesh()) == []
