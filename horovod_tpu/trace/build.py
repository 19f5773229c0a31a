"""The build ledger (docs/timeline.md "The build ledger"): what this process
traced, lowered and compiled or loaded, by function, and which kernel call
sites took their XLA form. Always on: every record is made where a program is
BUILT (JAX's own monitoring events, the plan decisions of ``ops/``), never
where one runs, so a step pays nothing for it.

Two clocks on every compile record, on purpose: ``end_perf_s`` is
``time.perf_counter()``, the clock a benchmark's window and host spans are on,
so "did anything compile inside the window" is a comparison of two numbers;
``end_wall_s`` is ``time.time()``, the clock of the span ring and of the
fleet merge (``tools/trace_merge.py``).

With tracing armed each compile record is also a ring event (``cat:
"compile"``, ``name: "<phase>:<fun>"``) and each fallback an instant
``hvd_kernel_fallback``; with metrics armed they count under ``hvd_jit_*``
and ``hvd_kernel_fallbacks_total`` (docs/metrics.md).
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Optional

CAPACITY = 4096

# The names the Pallas kernels carry in a device trace (``<name>.<n>``); a
# fallback record says which of them a call site did without.
KERNELS = ("attention", "flash_bwd", "gdn_fwd", "gdn_bwd", "moe_combine",
           "hc_mix_pre", "hc_mix_post", "hc_mix_post_bwd", "hc_mix_pre_bwd",
           "sparse_index_select", "sparse_index_kl", "ssd_fwd", "ssd_bwd")

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_PHASES = {
    _TRACE_EVENT: "trace",
    _LOWER_EVENT: "lower",
    # wraps compile_or_get_cached: a compile, or the load of a cached one
    "/jax/core/compile/backend_compile_duration": "compile",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_COUNTS = {   # both arrive inside the compile they belong to
    "/jax/compilation_cache/cache_hits": ("cache_hits", "hit"),
    "/jax/compilation_cache/cache_misses": ("cache_misses", "miss"),
}


def _fun(name: str) -> str:
    """One name a function over its phases: JAX names the trace ``step`` and
    the module it lowers and compiles ``jit(step)``."""
    for api in ("jit(", "pmap("):
        if name.startswith(api) and name.endswith(")"):
            return name[len(api):-1]
    return name


class BuildLedger:
    """Bounded, thread-safe, plain data. One a process (:data:`LEDGER`)."""

    def __init__(self, capacity: int = CAPACITY):
        self._lock = threading.Lock()
        self._compiles: "deque[dict]" = deque(maxlen=capacity)
        self._fallbacks: "deque[dict]" = deque(maxlen=capacity)
        # traces and lowerings open on this thread: JAX traces a nested jit
        # inside its caller's trace, and a lowering rule's helpers inside the
        # lowering, thousands of them a model; the record round them holds
        # their seconds, so of traces only the outermost is kept
        self._open = threading.local()
        # the objects handed to jax.monitoring, kept so that a second
        # install() finds and replaces exactly them
        self.listeners = (self._on_duration, self._on_event, self._on_scalar)
        self._import: Optional[tuple] = None   # once a process: reset keeps it
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._compiles.clear()
            self._fallbacks.clear()
            self._cache = {"cache_hits": 0, "cache_misses": 0,
                           "cache_retrieval_s": 0.0}
            self._plans: Dict[str, Any] = {}
            self._nested_traces = 0

    # ---------------------------------------------- jax.monitoring listeners
    def _on_scalar(self, event: str, value: float, **kw) -> None:
        if event in (_TRACE_EVENT, _LOWER_EVENT):   # a phase's start
            self._open.n = getattr(self._open, "n", 0) + 1

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        phase = _PHASES.get(event)
        if phase is None:
            if event == _RETRIEVAL:
                self._open.retrieval_s = float(duration)
                with self._lock:
                    self._cache["cache_retrieval_s"] += float(duration)
            return
        if phase != "compile":
            self._open.n = still_open = max(getattr(self._open, "n", 1) - 1, 0)
            if still_open and phase == "trace":
                with self._lock:
                    self._nested_traces += 1
                return
        rec = {"phase": phase, "fun": _fun(str(kw.get("fun_name", ""))),
               "dur_s": float(duration), "end_perf_s": time.perf_counter(),
               "end_wall_s": time.time()}
        if phase == "compile":
            # found in the persistent cache (and read in so many seconds),
            # written to it, or neither: both events arrive inside the compile
            rec["cache"] = self._open.__dict__.pop("cache", None)
            rec["cache_retrieval_s"] = self._open.__dict__.pop(
                "retrieval_s", 0.0)
        with self._lock:
            self._compiles.append(rec)
        _publish_compile(rec)

    def _on_event(self, event: str, **kw) -> None:
        key, word = _CACHE_COUNTS.get(event, (None, None))
        if key is None:
            return
        self._open.cache = word
        with self._lock:
            self._cache[key] += 1
        _count(f"hvd_jit_{key}_total")

    # ------------------------------------------------------- program's notes
    def note_import(self, start_perf_s: float, end_perf_s: float) -> None:
        with self._lock:
            if self._import is None:   # a reload is not the process's import
                self._import = (float(start_perf_s), float(end_perf_s))

    def note_plan(self, **kw) -> None:
        with self._lock:
            self._plans.update(
                {k: v for k, v in kw.items() if v is not None})

    def plan_args(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._plans)

    def note_fallback(self, op: str, reason: str, **shape) -> None:
        if op not in KERNELS:
            raise ValueError(f"{op!r} is no kernel of {KERNELS}")
        rec = {"op": op, "reason": reason, "shape": shape}
        with self._lock:
            self._fallbacks.append(rec)
        _publish_fallback(rec)

    # --------------------------------------------------------------- readers
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            start, end = self._import or (None, None)
            compiles = [dict(r) for r in self._compiles]
            stamps = [r["end_perf_s"] - r["dur_s"] for r in compiles]
            if start is not None:
                stamps.append(start)
            first = min(stamps, default=None)
            return {
                "import_s": None if start is None else end - start,
                "first_perf_s": first,
                "compiles": compiles,
                "nested_traces": self._nested_traces,
                "cache": dict(self._cache),
                "plans": dict(self._plans),
                "fallbacks": [dict(r, shape=dict(r["shape"]))
                              for r in self._fallbacks],
            }


def _count(name: str, value: float = 1.0, **labels) -> None:
    from .. import metrics as _metrics

    if _metrics.ACTIVE:
        _metrics.TAP.inc(name, value, **labels)


def _tap():
    """The live span ring when tracing is armed, else None."""
    pkg = sys.modules[__package__]
    return pkg.TAP if pkg.ACTIVE else None


def _publish_compile(rec: dict) -> None:
    tap = _tap()
    if tap is not None:
        tap.event(f"{rec['phase']}:{rec['fun']}", ph="X", cat="compile",
                  dur=rec["dur_s"], ts=rec["end_wall_s"] - rec["dur_s"])
    _count("hvd_jit_compiles_total", phase=rec["phase"])
    _count("hvd_jit_compile_seconds_total", rec["dur_s"], phase=rec["phase"])


def _publish_fallback(rec: dict) -> None:
    tap = _tap()
    if tap is not None:
        tap.event("hvd_kernel_fallback", cat="kernel", op=rec["op"],
                  reason=rec["reason"], **rec["shape"])
    _count("hvd_kernel_fallbacks_total", op=rec["op"], reason=rec["reason"])


try:
    LEDGER  # importlib.reload keeps the ledger whose listeners JAX holds
except NameError:
    LEDGER = BuildLedger()


def install_build_listeners() -> None:
    """Hand the ledger's listeners to ``jax.monitoring``; called when
    ``horovod_tpu.jax`` is imported, before any program is traced. Idempotent:
    however often it runs, and whatever ``clear_event_listeners()`` did in
    between, JAX holds each listener once."""
    from jax import monitoring

    on_duration, on_event, on_scalar = LEDGER.listeners
    for unregister, register, listener in (
        (monitoring.unregister_event_duration_listener,
         monitoring.register_event_duration_secs_listener, on_duration),
        (monitoring.unregister_event_listener,
         monitoring.register_event_listener, on_event),
        (monitoring.unregister_scalar_listener,
         monitoring.register_scalar_listener, on_scalar),
    ):
        try:
            unregister(listener)
        except (AssertionError, ValueError):   # not registered (any more)
            pass
        register(listener)


def note_import(start_perf_s: float, end_perf_s: float) -> None:
    """What importing the program cost this process: two
    ``time.perf_counter()`` stamps, top and bottom of ``horovod_tpu.jax``."""
    LEDGER.note_import(start_perf_s, end_perf_s)


def note_plan(**kw) -> None:
    """Record plan / correlation ids (fusion bucket plan, topo algorithm,
    wire dtype, a kernel's tiles): one flat dict, the last note of a key
    wins, ``None`` values dropped. Always recorded (call sites run at trace
    time, not per step); with tracing armed every step span carries them."""
    LEDGER.note_plan(**kw)


def plan_args() -> Dict[str, Any]:
    return LEDGER.plan_args()


def note_fallback(op: str, reason: str, **shape) -> None:
    """One record a traced call site that took a kernel's XLA form: ``op``
    of :data:`KERNELS`, why, and the shapes that decided it."""
    LEDGER.note_fallback(op, reason, **shape)


def build_ledger() -> Dict[str, Any]:
    """Plain data: ``import_s``, ``first_perf_s`` (the earliest stamp held),
    ``compiles`` (``phase``, ``fun``, ``dur_s``, ``end_perf_s``,
    ``end_wall_s``, and on a ``compile`` record ``cache``: ``"hit"``,
    ``"miss"`` or None, and ``cache_retrieval_s``; of traces the outermost, ``nested_traces`` counts the
    rest), ``cache`` (``cache_hits``, ``cache_misses``,
    ``cache_retrieval_s``), ``plans``, ``fallbacks`` (``op``, ``reason``,
    ``shape``)."""
    return LEDGER.snapshot()


def reset_build_ledger() -> None:
    LEDGER.reset()
