"""Fleet-wide distributed tracing (docs/timeline.md "Fleet tracing").

The per-rank catapult timeline (``utils/timeline.py``) answers "what did
THIS process do"; this package makes the fleet answerable as ONE
artifact:

- **Span ring + KV shipping**: every rank keeps a bounded in-memory ring
  of recent spans/events (wall-clock stamped) and a background pusher
  ships the window to the driver over the existing KV rendezvous plane
  (same pattern as the metrics snapshot pusher). ``tools/trace_merge.py``
  renders the driver-collected windows as one Perfetto/Chrome trace with
  one process lane per rank plus the driver's elastic/HA events on their
  own lane.
- **Step spans + straggler attribution**: ``make_train_step`` (and the
  elastic ``State.commit`` seam) record host-side step-boundary
  timestamps with the step index and the active plan/correlation ids
  (fusion path, topo plan algorithm, ``wire_dtype``); the driver compares
  per-step end times across ranks into the ``hvd_step_skew_seconds``
  histogram and ``hvd_straggler_total{rank}`` counters.
- **Flight recorder**: the ring doubles as an always-on crash recorder —
  dumped atomically (``utils/checkpoint.py`` tmp+fsync+replace
  discipline) on guard abort, stall-ladder escalation, SIGTERM, and
  uncaught crashes, so "the last N seconds before death, all ranks,
  aligned" survives the process (``tools/trace_merge.py --postmortem``).
- **Build ledger** (``build.py``): what this process traced, lowered and
  compiled or loaded, by function, the plan notes of the fusion, topology
  and kernel layers, and every kernel call site that took its XLA form.
  ALWAYS on, the one exception to the discipline below: all of it happens
  where a program is built, none of it where a step runs. Armed, the
  compiles are ring events too and the notes ride every step span.

Tap discipline — identical to ``fault/injector.py`` / ``metrics`` /
``guard``: with no trace knob set (the production default) the
module-level :data:`ACTIVE` flag is False, :data:`TAP` IS the shared
no-op singleton :data:`NULL_TAP`, instrumented call sites skip the tap
entirely (``if _trace.ACTIVE: ...`` is the whole overhead), and
:func:`wrap_step` returns the step function UNCHANGED (``wrap_step(f)
is f`` — the zero-overhead proof the tests assert).

Clock caveat: rings are stamped with ``time.time()`` (wall clock). The
per-worker offset the pusher estimates against the driver's ``/clock``
endpoint (KV ping RTT/2) is RECORDED as trace metadata, never silently
applied — cross-rank comparisons in the merged trace must be read with
the per-lane ``hvd_clock_offset`` metadata in hand (docs/timeline.md).
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from .build import (  # noqa: F401 (re-exported: the build ledger)
    KERNELS,
    _count,
    build_ledger,
    install_build_listeners,
    note_fallback,
    note_import,
    note_plan,
    plan_args,
    reset_build_ledger,
)

logger = logging.getLogger("horovod_tpu.trace")

TRACE_ENV = "HOROVOD_TRACE"
TRACE_DIR_ENV = "HOROVOD_TRACE_DIR"
TRACE_RING_ENV = "HOROVOD_TRACE_RING_EVENTS"
TRACE_PUSH_INTERVAL_ENV = "HOROVOD_TRACE_PUSH_INTERVAL_S"
TRACE_STRAGGLER_THRESHOLD_ENV = "HOROVOD_TRACE_STRAGGLER_THRESHOLD_S"

# KV scope worker trace windows are pushed under (driver-side collection
# reads the same scope; mirrors metrics/export.KV_SCOPE).
KV_SCOPE = "trace"

DEFAULT_RING_EVENTS = 2048
DEFAULT_STRAGGLER_THRESHOLD_S = 0.01

# Current flight-dump / pushed-window schema.
SCHEMA = 1

# Scopes inside the compiled step (docs/timeline.md "Scopes in the compiled
# step"): ``jax.named_scope`` names, always on — metadata on the HLO, nothing
# at run time. The device trace carries them as each op's scope path, which
# is what ``benchmark/scope_reduce.py`` groups a step's device time by.
SCOPE_LOSS_GRAD = "hvd_loss_grad"
SCOPE_EXCHANGE = "hvd_exchange"
SCOPE_EXCHANGE_PACK = SCOPE_EXCHANGE + "/pack"
SCOPE_EXCHANGE_REDUCE = SCOPE_EXCHANGE + "/reduce"
SCOPE_EXCHANGE_UNPACK = SCOPE_EXCHANGE + "/unpack"
SCOPE_OPTIMIZER = "hvd_optimizer"
SCOPE_GUARD = "hvd_guard"
SCOPE_FLASH_BWD = "flash_bwd"
# The layers of models/qwen3_next.py (docs/models.md): entered in the model,
# in ops/gated_delta.py's callers and in parallel/ep.py's dropless layer.
SCOPE_GDN_CONV = "gdn_conv"
SCOPE_GDN_SCAN = "gdn_scan"        # the chunked rule alone, no projection
SCOPE_GATED_ATTN = "gated_attn"
SCOPE_MOE_ROUTE = "moe_route"      # router, top-k, sort, gather, combine
SCOPE_MOE_EXPERTS = "moe_experts"  # the grouped products
SCOPE_MOE_SHARED = "moe_shared"
# The mixers of models/lfm2_moe.py (LFM2_SCOPES below: its expert layer
# enters moe_route and moe_experts). MODEL_SCOPES stays the first hybrid's
# six, which benchmark/scope_groups/qwen3_next.json lists one for one.
SCOPE_SHORT_CONV = "short_conv"    # the two gates and the taps, no projection
SCOPE_GQA_ATTN = "gqa_attn"
# The layers of models/xing4.py (XING4_SCOPES below): the latent-attention
# mixer outside the flash kernels, and the two stream mixes of a layer.
SCOPE_LATENT_ATTN = "latent_attn"  # low-rank products, norms, rotary, k, W_o
SCOPE_HC_MIX = "hc_mix"            # mixing matrices, read and write of streams
# The layers of models/keye_vl.py (KEYE_SCOPES below: gqa_attn is its
# attention outside the kernels, as LFM2's): the indexer in front of
# attention (ops/sparse_index.py) and the objective that trains it.
SCOPE_SPARSE_INDEX = "sparse_index"  # projections, scores, k-th value, mask
SCOPE_SPARSE_INDEX_LOSS = "sparse_index_loss"  # head-summed p, KL, backward
# The state-space layers of models/nemotron_h.py (NEMOTRON_H_SCOPES below:
# its attention and expert layers enter gqa_attn and the moe_ scopes): the
# mixer's projections and gated norm, and inside it the convolution and the
# selective scan (ops/ssd.py), which ssm_ms.train reads.
SCOPE_SSM_MIXER = "ssm_mixer"
SCOPE_SSM_CONV = "ssm_conv"        # taps, bias, silu; no projection
SCOPE_SSM_SCAN = "ssm_scan"        # decays, chunk products, chunk pass, D x
# The Kimi-delta-attention layers of models/ling.py (LING_SCOPES below: its
# latent-attention layer enters latent_attn and its expert layers the moe_
# scopes): the whole mixer, and inside it the three convolutions and the
# per-channel delta rule (ops/kda.py), which kda_ms.train reads.
SCOPE_KDA_MIXER = "kda_mixer"
SCOPE_KDA_CONV = "kda_conv"        # taps and silu; no projection
SCOPE_KDA_SCAN = "kda_scan"        # gate, sums, chunk products, chunk pass
MODEL_SCOPES = (
    SCOPE_GDN_CONV,
    SCOPE_GDN_SCAN,
    SCOPE_GATED_ATTN,
    SCOPE_MOE_ROUTE,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_SHARED,
)
LFM2_SCOPES = (
    SCOPE_SHORT_CONV,
    SCOPE_GQA_ATTN,
    SCOPE_MOE_ROUTE,
    SCOPE_MOE_EXPERTS,
)
XING4_SCOPES = (
    SCOPE_LATENT_ATTN,
    SCOPE_HC_MIX,
    SCOPE_MOE_ROUTE,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_SHARED,
)
KEYE_SCOPES = (
    SCOPE_GQA_ATTN,
    SCOPE_SPARSE_INDEX,
    SCOPE_SPARSE_INDEX_LOSS,
    SCOPE_MOE_ROUTE,
    SCOPE_MOE_EXPERTS,
)
NEMOTRON_H_SCOPES = (
    SCOPE_SSM_MIXER,
    SCOPE_SSM_CONV,
    SCOPE_SSM_SCAN,
    SCOPE_GQA_ATTN,
    SCOPE_MOE_ROUTE,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_SHARED,
)
LING_SCOPES = (
    SCOPE_KDA_MIXER,
    SCOPE_KDA_CONV,
    SCOPE_KDA_SCAN,
    SCOPE_LATENT_ATTN,
    SCOPE_MOE_ROUTE,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_SHARED,
)
STEP_SCOPES = (
    SCOPE_LOSS_GRAD,
    SCOPE_EXCHANGE_PACK,
    SCOPE_EXCHANGE_REDUCE,
    SCOPE_EXCHANGE_UNPACK,
    SCOPE_OPTIMIZER,
    SCOPE_GUARD,
    SCOPE_FLASH_BWD,
)


def _ring_capacity() -> int:
    try:
        n = int(os.environ.get(TRACE_RING_ENV, "") or DEFAULT_RING_EVENTS)
    except ValueError:
        n = DEFAULT_RING_EVENTS
    return max(n, 16)


def straggler_threshold_s() -> float:
    """Cross-rank step skew above which the slowest rank is charged one
    ``hvd_straggler_total{rank}`` count (driver-side)."""
    try:
        return float(
            os.environ.get(TRACE_STRAGGLER_THRESHOLD_ENV, "")
            or DEFAULT_STRAGGLER_THRESHOLD_S
        )
    except ValueError:
        return DEFAULT_STRAGGLER_THRESHOLD_S


def trace_dir() -> Optional[str]:
    """Directory for flight-recorder dumps and driver-collected rank
    windows (None = flight dumps disabled)."""
    d = os.environ.get(TRACE_DIR_ENV, "").strip()
    return d or None


def _rank() -> int:
    v = os.environ.get("HOROVOD_RANK", "")
    return int(v) if v.isdigit() else 0


class TraceTap:
    """The live tap: a thread-safe bounded ring of span/event records
    plus the step ledger the straggler attribution feeds on.

    Record shape (plain dicts so windows JSON through the KV plane
    unchanged): ``{"name", "ph" ("X"|"i"|"B"|"E"|"M"), "ts" (wall-clock
    seconds), "dur" (seconds, "X" only), "cat", "tid", "args"}``."""

    def __init__(self, ring_capacity: Optional[int] = None):
        cap = ring_capacity or _ring_capacity()
        self._lock = threading.Lock()
        self._ring: "deque[dict]" = deque(maxlen=cap)
        # (step_index, t_begin, t_end) wall-clock step boundaries — the
        # feed the driver's skew tracker consumes.
        self._steps: "deque[tuple]" = deque(maxlen=cap)
        self._step_idx = 0
        # Wrapped-step activity: while a wrap_step tap is recording real
        # step spans, the State.commit marker stays a plain instant so
        # one training step is never double-counted in the skew feed.
        self._wrapped_steps = 0
        self._last_commit_t: Optional[float] = None
        self._commit_idx = 0
        # Clock-offset estimate vs the driver (recorded metadata, never
        # applied to timestamps).
        self.clock: Dict[str, Any] = {
            "offset_s": 0.0, "rtt_s": 0.0, "estimated": False,
        }
        self.rank = _rank()

    # ------------------------------------------------------------ record
    def event(self, name: str, ph: str = "i", cat: str = "event",
              dur: Optional[float] = None, ts: Optional[float] = None,
              tid: int = 0, **args) -> dict:
        rec: Dict[str, Any] = {
            "name": name,
            "ph": ph,
            "ts": time.time() if ts is None else float(ts),
            "cat": cat,
            "tid": int(tid),
        }
        if dur is not None:
            rec["dur"] = float(dur)
        if args:
            rec["args"] = args
        with self._lock:
            self._ring.append(rec)
        return rec

    def timeline_event(self, ev: dict) -> None:
        """Mirror one catapult-timeline record into the ring (wall-clock
        restamped — the timeline's own clock is perf_counter-relative).
        Called from ``utils/timeline.py`` under the ACTIVE gate."""
        rec = {
            "name": ev.get("name", ""),
            "ph": ev.get("ph", "i"),
            "ts": time.time(),
            "cat": "timeline",
            "tid": int(ev.get("tid", 0) or 0),
        }
        args = ev.get("args")
        if args:
            rec["args"] = args
        with self._lock:
            self._ring.append(rec)

    # ------------------------------------------------------- step spans
    def begin_step(self):
        with self._lock:
            idx = self._step_idx
            self._step_idx += 1
        return idx, time.time()

    def end_step(self, token, **args) -> None:
        idx, t0 = token
        t1 = time.time()
        rec = {
            "name": "hvd_step",
            "ph": "X",
            "ts": t0,
            "dur": t1 - t0,
            "cat": "step",
            "tid": 0,
            "args": {"step": idx, **plan_args(), **args},
        }
        with self._lock:
            self._ring.append(rec)
            self._steps.append((idx, t0, t1))
            self._wrapped_steps += 1

    def commit_step(self, **args) -> None:
        """Mark one elastic commit boundary (``State.commit``). Between
        two commits lies exactly one training step for loops that commit
        per step, so the inter-commit window doubles as the step span —
        unless a :func:`wrap_step` tap is already recording real step
        spans, in which case this stays a plain instant marker (no
        double-counting in the skew feed)."""
        now = time.time()
        with self._lock:
            wrapped = self._wrapped_steps > 0
            last = self._last_commit_t
            self._last_commit_t = now
            idx = self._commit_idx
            self._commit_idx += 1
            self._ring.append({
                "name": "hvd_commit",
                "ph": "i",
                "ts": now,
                "cat": "step",
                "tid": 0,
                "args": {"commit": idx, **args},
            })
            if not wrapped and last is not None:
                self._steps.append((idx - 1, last, now))

    # ------------------------------------------------------- shipping
    def window(self) -> Dict[str, Any]:
        """The pushable/dumpable view of this rank's recent activity —
        plain data only, bounded by the ring capacity. The window is
        stamped with the CURRENT elastic generation (0 outside elastic
        runs): rank numbers are only meaningful within a generation, so
        the driver's skew attribution must never mix windows across a
        resize (a renumbered or departed rank would be charged for a
        stranger's steps)."""
        with self._lock:
            events = [dict(e) for e in self._ring]
            steps = [list(s) for s in self._steps]
        gen = os.environ.get("HOROVOD_ELASTIC_GEN", "")
        return {
            "schema": SCHEMA,
            "rank": self.rank,
            "gen": int(gen) if gen.isdigit() else 0,
            "clock": dict(self.clock),
            "plan": plan_args(),
            "events": events,
            "steps": steps,
        }

    def reset_steps(self) -> None:
        """Restart the step ledger at a world re-formation boundary:
        after an elastic resize ranks are renumbered and a freshly
        promoted worker starts counting from 0, so carrying the old
        cumulative step indices across the generation would misalign
        every cross-rank comparison. The event ring is kept (history is
        still history); only the step-index feed restarts."""
        with self._lock:
            self._steps.clear()
            self._step_idx = 0
            self._wrapped_steps = 0
            self._last_commit_t = None
            self._commit_idx = 0

    def set_clock(self, offset_s: float, rtt_s: float) -> None:
        self.clock = {
            "offset_s": float(offset_s),
            "rtt_s": float(rtt_s),
            "estimated": True,
        }
        self.event(
            "hvd_clock_offset", ph="M", cat="clock",
            offset_s=float(offset_s), rtt_s=float(rtt_s),
        )

    # -------------------------------------------------- flight recorder
    def flight_dump(self, reason: str,
                    directory: Optional[str] = None) -> Optional[str]:
        """Atomically persist the ring (checkpoint.py tmp+fsync+replace
        discipline) as this rank's flight-recorder dump. Returns the
        path, or None when no trace directory is configured. Must never
        raise — it runs on abort/crash paths."""
        try:
            d = directory or trace_dir()
            if not d:
                logger.warning(
                    "flight recorder: no %s configured; dropping the "
                    "%r dump", TRACE_DIR_ENV, reason,
                )
                return None
            os.makedirs(d, exist_ok=True)
            doc = self.window()
            doc["reason"] = reason
            doc["dumped_at"] = time.time()
            payload = json.dumps(doc, sort_keys=True).encode()
            path = os.path.join(d, f"flight.rank{self.rank}.json")
            from ..utils.checkpoint import _atomic_write

            _atomic_write(path, lambda f: f.write(payload))
            _count("hvd_trace_flight_dumps_total", reason=reason)
            logger.warning(
                "flight recorder: dumped %d events to %s (reason: %s)",
                len(doc["events"]), path, reason,
            )
            return path
        except Exception:  # noqa: BLE001 - crash paths must stay crashable
            logger.exception("flight recorder dump failed")
            return None


class _NullTraceTap:
    """Shared no-op tap installed while tracing is disabled. Sites gate
    on :data:`ACTIVE` and never reach it; holders of a tap reference pay
    one empty method call."""

    rank = 0
    clock: Dict[str, Any] = {}

    def event(self, *a, **kw) -> dict:
        return {}

    def timeline_event(self, ev: dict) -> None:
        pass

    def begin_step(self):
        return (0, 0.0)

    def end_step(self, token, **args) -> None:
        pass

    def commit_step(self, **args) -> None:
        pass

    def window(self) -> Dict[str, Any]:
        return {}

    def reset_steps(self) -> None:
        pass

    def set_clock(self, offset_s: float, rtt_s: float) -> None:
        pass

    def flight_dump(self, reason: str,
                    directory: Optional[str] = None) -> Optional[str]:
        return None


NULL_TAP = _NullTraceTap()

ACTIVE = False
TAP: Any = NULL_TAP

_lock = threading.Lock()
_prev_excepthook = None


def enabled() -> bool:
    return ACTIVE


def tap():
    """The process-wide tap: the live one when enabled, else the shared
    no-op singleton (``trace.tap() is trace.NULL_TAP``)."""
    return TAP


def _excepthook(exc_type, exc, tb):
    """Uncaught-crash hook: dump the flight ring, then defer to the
    previous hook (the default prints the traceback)."""
    try:
        if ACTIVE and not issubclass(exc_type, KeyboardInterrupt):
            TAP.flight_dump(f"crash:{exc_type.__name__}")
    except Exception:  # noqa: BLE001 - the hook must never mask the crash
        pass
    hook = _prev_excepthook or sys.__excepthook__
    hook(exc_type, exc, tb)


def install(active: bool) -> None:
    """(De)activate fleet tracing for this process. Activation arms the
    uncaught-crash flight-dump hook; deactivation restores the previous
    ``sys.excepthook``."""
    global ACTIVE, TAP, _prev_excepthook
    with _lock:
        if active:
            TAP = TraceTap()
            ACTIVE = True
            if sys.excepthook is not _excepthook:
                _prev_excepthook = sys.excepthook
                sys.excepthook = _excepthook
        else:
            TAP = NULL_TAP
            ACTIVE = False
            if sys.excepthook is _excepthook:
                sys.excepthook = _prev_excepthook or sys.__excepthook__
                _prev_excepthook = None


def activate_from_env() -> bool:
    v = os.environ.get(TRACE_ENV, "").strip().lower()
    on = v not in ("", "0", "false", "no", "off")
    # Pointing a trace dir at the recorder without the master switch
    # still arms it — the flight recorder is the always-on half.
    install(on or bool(os.environ.get(TRACE_DIR_ENV, "").strip()))
    return ACTIVE


def reset() -> None:
    install(False)


def wrap_step(fn, **meta):
    """Wrap a step function with the host-side step tap. With tracing
    disabled this returns ``fn`` ITSELF — the zero-overhead contract
    (``wrap_step(f) is f``) the tests assert. ``meta`` is stamped onto
    every step span's args alongside the noted plan/correlation ids."""
    if not ACTIVE:
        return fn
    from jax.profiler import StepTraceAnnotation

    tap_ref = TAP

    def traced_step(*args, **kwargs):
        token = tap_ref.begin_step()
        # The same span in the profiler's trace, when a session is open
        # (HOROVOD_PROFILER_DIR or the user's own): on the device's clock,
        # with the step number. Its duration is the ENQUEUE, like the
        # ring's: the jitted call returns before the device has finished.
        with StepTraceAnnotation("hvd_step", step_num=token[0]):
            out = fn(*args, **kwargs)
        tap_ref.end_step(token, **meta)
        return out

    traced_step.__wrapped__ = fn
    traced_step.__hvd_trace_wrapped__ = True
    traced_step.__name__ = getattr(fn, "__name__", "step")
    return traced_step


def flight_dump(reason: str) -> Optional[str]:
    """Module-level convenience for abort paths: dump when active, no-op
    otherwise."""
    if not ACTIVE:
        return None
    return TAP.flight_dump(reason)


# Re-exported for the driver/tools (lazy submodule import keeps worker
# import cost at zero when tracing is off).
def __getattr__(name: str):
    if name in ("pusher", "merge"):
        import importlib

        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(name)


# Arm at import (mirrors fault/injector.py, metrics, guard): worker
# processes spawned with HOROVOD_TRACE/HOROVOD_TRACE_DIR in their
# environment record without code changes.
if (os.environ.get(TRACE_ENV, "").strip()
        or os.environ.get(TRACE_DIR_ENV, "").strip()):
    try:
        activate_from_env()
    except Exception:  # noqa: BLE001 - a malformed knob must not take
        # down production init; surfaced by the trace tools/tests.
        logger.exception("could not arm fleet tracing from env")
