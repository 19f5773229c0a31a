"""The build ledger (``horovod_tpu/trace/build.py``, docs/timeline.md "The
build ledger"): always on, fed by JAX's monitoring events and by the plan
decisions of ``ops/``; armed, it also writes into the span ring and the
metrics registry."""

import importlib
import time

import jax
import jax.numpy as jnp
import pytest

import horovod_tpu.jax  # noqa: F401 - installs the ledger's listeners
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu import trace as hvd_trace
from horovod_tpu.trace import build


@pytest.fixture(autouse=True)
def _clean_state():
    hvd_trace.reset()
    hvd_metrics.reset()
    hvd_trace.reset_build_ledger()
    yield
    hvd_trace.reset()
    hvd_metrics.reset()
    hvd_trace.reset_build_ledger()


def _records(fun):
    return [(r["phase"], r["fun"])
            for r in hvd_trace.build_ledger()["compiles"] if r["fun"] == fun]


def _registered():
    from jax._src import monitoring

    on_duration, on_event, on_scalar = build.LEDGER.listeners
    return (monitoring.get_event_duration_listeners().count(on_duration),
            monitoring.get_event_listeners().count(on_event),
            monitoring.get_scalar_listeners().count(on_scalar))


# ------------------------------------------------------------- always on
def test_on_with_no_knob_set_and_the_step_is_still_unwrapped():
    assert not hvd_trace.ACTIVE and hvd_trace.TAP is hvd_trace.NULL_TAP
    assert not hvd_metrics.ACTIVE

    def ledger_probe_unarmed(x):
        return x * 3

    assert hvd_trace.wrap_step(ledger_probe_unarmed) is ledger_probe_unarmed
    jax.jit(ledger_probe_unarmed)(jnp.ones(3))
    assert len(_records("ledger_probe_unarmed")) == 3
    assert hvd_trace.build_ledger()["import_s"] > 0


def test_a_new_shape_is_one_trace_one_lower_one_compile():
    @jax.jit
    def ledger_probe_shapes(x):
        return x + 1

    t0, w0 = time.perf_counter(), time.time()
    ledger_probe_shapes(jnp.ones(5))
    assert sorted(_records("ledger_probe_shapes")) == [
        ("compile", "ledger_probe_shapes"), ("lower", "ledger_probe_shapes"),
        ("trace", "ledger_probe_shapes")]
    ledger_probe_shapes(jnp.ones(5))          # the cached executable
    assert len(_records("ledger_probe_shapes")) == 3
    ledger_probe_shapes(jnp.ones(7))
    assert len(_records("ledger_probe_shapes")) == 6
    # both clocks, each record's end inside this test
    for r in hvd_trace.build_ledger()["compiles"]:
        assert t0 <= r["end_perf_s"] <= time.perf_counter()
        assert w0 <= r["end_wall_s"] <= time.time()
        assert r["dur_s"] >= 0


def test_of_nested_traces_the_outermost_is_kept():
    """JAX traces a jit called inside a jit inside its caller's trace: a
    model is thousands of them, and the caller's record holds their time."""
    @jax.jit
    def ledger_probe_inner(x):
        return x * 2

    @jax.jit
    def ledger_probe_outer(x):
        return ledger_probe_inner(x) + ledger_probe_inner(x + 1)

    ledger_probe_outer(jnp.ones(17))
    assert len(_records("ledger_probe_outer")) == 3
    assert _records("ledger_probe_inner") == []
    assert hvd_trace.build_ledger()["nested_traces"] >= 1
    ledger_probe_inner(jnp.ones(19))          # called alone, it is outermost
    assert len(_records("ledger_probe_inner")) == 3


def test_listeners_are_registered_once_whatever_happens():
    assert _registered() == (1, 1, 1)
    hvd_trace.install_build_listeners()   # what importing the program does
    ledger = build.LEDGER
    importlib.reload(build)               # keeps the ledger JAX listens with
    assert build.LEDGER is ledger
    build.install_build_listeners()
    assert _registered() == (1, 1, 1)
    jax.monitoring.clear_event_listeners()
    assert _registered()[:2] == (0, 0)    # (JAX 0.9.0 leaves the scalar ones)
    hvd_trace.install_build_listeners()
    assert _registered() == (1, 1, 1)


@pytest.fixture()
def disk_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    names = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1,
             "jax_enable_compilation_cache": True}
    saved = {n: getattr(jax.config, n) for n in names}
    for n, v in names.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_a_compile_that_finds_its_executable_on_disk_counts_a_hit(disk_cache):
    def ledger_probe_cached(x):
        return jnp.sin(x) * 2

    jax.jit(ledger_probe_cached)(jnp.ones(11))
    cache = hvd_trace.build_ledger()["cache"]
    assert cache["cache_misses"] >= 1 and cache["cache_hits"] == 0
    jax.clear_caches()                    # what a second process starts with
    jax.jit(ledger_probe_cached)(jnp.ones(11))
    cache = hvd_trace.build_ledger()["cache"]
    assert cache["cache_hits"] >= 1 and cache["cache_retrieval_s"] > 0
    # the load is a `compile` record all the same (compile-or-load), and
    # each record says how the cache answered
    mine = [r for r in hvd_trace.build_ledger()["compiles"]
            if r["fun"] == "ledger_probe_cached" and r["phase"] == "compile"]
    assert [r["cache"] for r in mine] == ["miss", "hit"]
    assert mine[0]["cache_retrieval_s"] == 0.0 < mine[1]["cache_retrieval_s"]


# ------------------------------------------------- plan notes, fallbacks
def test_plan_notes_are_recorded_with_tracing_off_and_ride_spans_armed():
    assert not hvd_trace.ACTIVE
    hvd_trace.note_plan(fusion_path="posthoc", wire_dtype=None)
    hvd_trace.note_plan(fusion_path="streamed")        # the last note wins
    assert hvd_trace.plan_args() == {"fusion_path": "streamed"}
    assert hvd_trace.build_ledger()["plans"] == {"fusion_path": "streamed"}
    for tap in (hvd_trace.TraceTap(), hvd_trace.NULL_TAP):
        assert not hasattr(tap, "note_plan") and not hasattr(tap, "plan_args")
    hvd_trace.install(True)               # noted before arming, carried after
    hvd_trace.wrap_step(lambda: None)()
    assert hvd_trace.TAP.window()["plan"] == {"fusion_path": "streamed"}
    span = hvd_trace.TAP.window()["events"][-1]
    assert span["name"] == "hvd_step"
    assert span["args"]["fusion_path"] == "streamed"


def test_delta_rule_records_a_fallback_a_call_site_and_none_for_the_kernel():
    from horovod_tpu.ops.gated_delta import gated_delta_chunked

    def args(dk):
        z = lambda *s: jnp.zeros(s, jnp.float32)
        return z(1, 256, 1, dk), z(1, 256, 1, dk), z(1, 256, 2, 128), \
            z(1, 256, 2), z(1, 256, 2)

    traced = lambda *a, **kw: jax.make_jaxpr(
        lambda *x: gated_delta_chunked(*x, **kw))(*a)
    traced(*args(128), chunk=64)                       # the kernels' shapes
    assert hvd_trace.build_ledger()["fallbacks"] == []
    assert hvd_trace.plan_args()["gdn_kernel"] is True
    traced(*args(128), chunk=64, initial_state=jnp.zeros((1, 2, 128, 128)))
    traced(*args(16), chunk=64)
    traced(*args(16), chunk=64)            # a second call site, not a rewrite
    fallbacks = hvd_trace.build_ledger()["fallbacks"]
    assert [(f["op"], f["reason"]) for f in fallbacks] == [
        ("gdn_fwd", "initial_state"),
        ("gdn_fwd", "head_width_not_whole_lanes"),
        ("gdn_fwd", "head_width_not_whole_lanes")]
    assert fallbacks[1]["shape"] == {"batch": 1, "seq": 256, "heads": 2,
                                     "chunk": 64, "dk": 16, "dv": 128}


def test_gather_sum_records_a_fallback_where_a_row_is_no_whole_lanes():
    from horovod_tpu.ops.moe_combine import gather_sum

    def traced(width):
        rows = jnp.zeros((64, width), jnp.float32)
        pos = jnp.zeros((16, 2), jnp.int32)
        return jax.make_jaxpr(gather_sum)(rows, pos, jnp.ones((16, 2)))

    traced(1024)                                       # the kernel's shape
    assert hvd_trace.build_ledger()["fallbacks"] == []
    traced(64)
    assert hvd_trace.build_ledger()["fallbacks"] == [{
        "op": "moe_combine", "reason": "row_not_whole_lanes",
        "shape": {"tokens": 16, "slots": 2, "width": 64}}]


def test_dense_attention_records_a_fallback_and_the_kernel_none(monkeypatch):
    from horovod_tpu.ops import pallas_attention as pa
    from horovod_tpu.ops.pallas_attention import flash_attention_bthd

    monkeypatch.setattr(pa, "_PREF_BLOCK", 16)
    traced = lambda t: jax.make_jaxpr(
        lambda q: flash_attention_bthd(q, q, q, causal=True))(
            jnp.zeros((1, t, 2, 16), jnp.float32))
    traced(64)
    assert hvd_trace.build_ledger()["fallbacks"] == []
    traced(67)                 # a prime length past a block: none divides
    assert hvd_trace.build_ledger()["fallbacks"] == [{
        "op": "attention", "reason": "no_block_divisor",
        "shape": {"t_q": 67, "t_k": 67, "heads": 2, "head_dim": 16}}]


def test_a_fallback_names_a_kernel_of_the_device_trace():
    assert hvd_trace.KERNELS == (
        "attention", "flash_bwd", "gdn_fwd", "gdn_bwd", "moe_combine",
        "hc_mix_pre", "hc_mix_post", "hc_mix_post_bwd", "hc_mix_pre_bwd",
        "sparse_index_select", "sparse_index_kl", "ssd_fwd", "ssd_bwd")
    with pytest.raises(ValueError, match="no kernel"):
        hvd_trace.note_fallback("softmax", "whatever")
    assert hvd_trace.build_ledger()["fallbacks"] == []


# ------------------------------------------------------------------ armed
def test_armed_a_compile_is_a_ring_event_and_the_counters_rise():
    hvd_trace.install(True)
    hvd_metrics.install(True)

    @jax.jit
    def ledger_probe_armed(x):
        return x - 1

    w0 = time.time()
    ledger_probe_armed(jnp.ones(13))
    w1 = time.time()
    hvd_trace.note_fallback("moe_combine", "row_not_whole_tiles", width=64)
    events = hvd_trace.TAP.window()["events"]
    mine = {e["name"]: e for e in events if e["cat"] == "compile"
            and e["name"].endswith(":ledger_probe_armed")}
    assert set(mine) == {"trace:ledger_probe_armed",
                         "lower:ledger_probe_armed",
                         "compile:ledger_probe_armed"}
    for e in mine.values():               # a span on the ring's wall clock
        assert e["ph"] == "X" and w0 <= e["ts"] <= e["ts"] + e["dur"] <= w1
    fallback = [e for e in events if e["name"] == "hvd_kernel_fallback"]
    assert len(fallback) == 1 and fallback[0]["ph"] == "i"
    assert fallback[0]["args"] == {"op": "moe_combine", "width": 64,
                                   "reason": "row_not_whole_tiles"}

    snap = hvd_metrics.TAP.snapshot()

    def total(name, **labels):
        return sum(s["value"] for s in snap[name]["series"]
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    for phase in ("trace", "lower", "compile"):
        assert total("hvd_jit_compiles_total", phase=phase) >= 1
        assert total("hvd_jit_compile_seconds_total", phase=phase) > 0
    assert total("hvd_kernel_fallbacks_total", op="moe_combine",
                 reason="row_not_whole_tiles") == 1
    for name in ("hvd_jit_compiles_total", "hvd_jit_compile_seconds_total",
                 "hvd_jit_cache_hits_total", "hvd_jit_cache_misses_total",
                 "hvd_kernel_fallbacks_total"):
        assert name in hvd_metrics._CATALOG


def test_the_ledger_is_bounded():
    ledger = build.BuildLedger(capacity=8)
    for i in range(50):
        ledger._on_duration("/jax/core/compile/backend_compile_duration",
                            0.001, fun_name=f"jit(f{i})")
        ledger.note_fallback("attention", "no_block_divisor", t_q=i)
    snap = ledger.snapshot()
    assert [r["fun"] for r in snap["compiles"]] == [
        f"f{i}" for i in range(42, 50)]
    assert [f["shape"]["t_q"] for f in snap["fallbacks"]] == list(
        range(42, 50))
    assert build.CAPACITY == 4096
    ledger._on_duration("/jax/some/other/event", 1.0)         # not a phase
    assert len(ledger.snapshot()["compiles"]) == 8
