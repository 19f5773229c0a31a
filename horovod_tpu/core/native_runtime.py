"""Runtime backed by the native control-plane core (cpp/libhvd_core.so).

Division of labor (TPU-native re-design of the reference architecture):
the C++ core owns the background cycle loop, cross-rank negotiation,
fusion planning, response cache, stall detection, timeline, and autotune —
everything the reference keeps in ``horovod/common/*.cc``. Tensor payloads
never cross the ABI: Python keeps the arrays, receives fused execution
Plans, runs them on the XLA data plane, and reports completion (which feeds
the core's autotuner and timeline).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional

import numpy as np

from ..common.basics import NativeCore, _CoreError
from ..common.env import Config
from ..common.topology import Topology
from ..fault import injector as _fault
from .. import guard as _guard
from .. import metrics as _metrics
from .. import trace as _trace
from ..common.types import (
    DataType,
    ReduceOp,
    RequestType,
    Status,
    StatusType,
    TensorTableEntry,
    dtype_from_array,
    dtype_name,
)

logger = logging.getLogger("horovod_tpu")

_PLAN_ERROR = 7  # ResponseType::kError
_PLAN_JOIN = 3

# Plan type → metrics op label (matches ResponseType ordering in the
# native core and the Python runtime's timeline names).
_PLAN_TYPE_NAMES = {
    0: "ALLREDUCE", 1: "ALLGATHER", 2: "BROADCAST", 3: "JOIN",
    4: "ALLTOALL", 5: "REDUCESCATTER", 6: "ADASUM", 7: "ERROR",
}


class PlanExecutor:
    """Executes one fused plan's entries; returns {name: output}."""

    def execute(self, plan: dict, entries, topo: Topology) -> Dict[str, Any]:
        raise NotImplementedError


class LocalPlanExecutor(PlanExecutor):
    """size=1 executor: collectives are (scaled) identities."""

    def execute(self, plan: dict, entries, topo: Topology) -> Dict[str, Any]:
        outputs: Dict[str, Any] = {}
        participants = max(int(plan.get("participants", 1)), 1)
        for entry in entries:
            t = entry.tensor
            if plan["type"] in (0, 6):  # allreduce / adasum
                factor = entry.prescale_factor * entry.postscale_factor
                if entry.reduce_op == ReduceOp.AVERAGE:
                    factor /= participants
                outputs[entry.name] = t if factor == 1.0 else t * factor
            else:
                outputs[entry.name] = t
        return outputs


class NativeRuntime:
    """Drop-in replacement for core.runtime.Runtime, backed by the C++
    core. Same producer API; the executor thread replaces the Python
    background loop."""

    def __init__(
        self,
        config: Config,
        topology: Topology,
        executor: Optional[PlanExecutor] = None,
        coord_addr: str = "",
        coord_port: int = 0,
    ):
        self.config = config
        self.topology = topology
        if executor is None:
            if topology.size > 1:
                raise NotImplementedError(
                    f"Eager mode for size={topology.size} requires a "
                    "multi-process plan executor (launcher-provided); use "
                    "the compiled mode (horovod_tpu.jax) or run "
                    "single-process."
                )
            executor = LocalPlanExecutor()
        self.executor = executor
        self.core = NativeCore()
        self.core.init(config, topology, coord_addr, coord_port)
        # Per-name FIFO: a name may be legally re-enqueued while its
        # predecessor's plan is still executing; the core dispatches plans
        # in acceptance order, so popleft matches plan order.
        self._entries: Dict[str, "deque[TensorTableEntry]"] = {}
        self._entries_lock = threading.Lock()
        self._outputs: Dict[str, 'deque'] = {}  # name -> FIFO of outputs
        self._ticket_names: Dict[int, str] = {}
        self._done: Dict[int, tuple] = {}
        self._cv = threading.Condition()
        # Inline execution fast path: a caller blocked in
        # synchronize() is a hot, already-scheduled thread — letting IT
        # pop and run the plan skips the executor-thread wakeup hop
        # entirely, and since every rank's caller spins the same way,
        # the ranks reach the collective aligned instead of paying each
        # other's wake latency inside it. Pop+execute is one atomic unit
        # under this lock, so plans still execute strictly in the core's
        # dispatch order no matter which thread consumes them. RLock:
        # a completion callback may legally synchronize() another handle
        # (nested consumption by the same thread must not deadlock).
        self._consumer_lock = threading.RLock()
        self._inline_sync = os.environ.get(
            "HOROVOD_INLINE_SYNC", "1"
        ) not in ("0", "false")
        self._flush_hint = os.environ.get(
            "HOROVOD_FLUSH_HINT", "1"
        ) not in ("0", "false")
        # Count of threads currently blocked in synchronize(): while any
        # exist, the executor thread parks so the hot thread wins the
        # consumer role (with a plain race, the executor — usually
        # already blocked inside next_plan's C++ wait — would keep
        # winning and the fast path would never engage). _no_waiters is
        # the park signal: set while the count is zero, so the executor
        # blocks on it instead of busy-polling and wakes the moment the
        # last waiter leaves.
        self._sync_waiters = 0
        self._no_waiters = threading.Event()
        self._no_waiters.set()
        # Set by an inline synchronize() that observes next_plan == -1
        # (core down): the parked executor thread must run its
        # orphaned-entry drain NOW, not after every waiter exits — a TF
        # callback-consumer with no handle to fail would otherwise hang
        # until the last handle-waiter left (advisor finding,
        # native_runtime inline-sync drain deferral).
        self._core_down = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._executor_loop, name="hvd_plan_executor", daemon=True
        )
        self._thread.start()

    # --- lifecycle ---
    def start(self) -> None:  # parity with python Runtime
        pass

    @property
    def running(self) -> bool:
        return not self._stop.is_set() and self.core.initialized()

    def shutdown(self) -> None:
        if self._stop.is_set():
            return
        self._stop.set()
        self.core.shutdown()
        self._thread.join(timeout=30.0)
        with self._cv:
            for t, name in list(self._ticket_names.items()):
                if t not in self._done:
                    self._done[t] = (
                        Status.Aborted("Horovod has been shut down."),
                        None,
                    )
            self._cv.notify_all()

    # --- runtime timeline control (later-reference API) ---
    def start_timeline(self, file_path: str, mark_cycles: bool = False):
        code = self.core.start_timeline(file_path, mark_cycles)
        if code:
            raise ValueError(
                f"could not start timeline at {file_path!r} "
                f"(status {code}: already active, or unwritable path)"
            )

    def stop_timeline(self) -> None:
        self.core.stop_timeline()

    # --- enqueue API ---
    def _enqueue(
        self,
        request_type: RequestType,
        name: str,
        tensor: Any,
        *,
        root_rank: int = -1,
        reduce_op: ReduceOp = ReduceOp.SUM,
        prescale_factor: float = 1.0,
        postscale_factor: float = 1.0,
        callback: Optional[Callable] = None,
        group_id: int = 0,
        group_size: int = 0,
        process_set_id: int = 0,
    ) -> int:
        if not self.running:
            from .. import HorovodInternalError

            raise HorovodInternalError(
                "Horovod runtime is shut down or was never initialized; "
                "call hvd.init() first."
            )
        if _fault.ACTIVE:
            # Chaos tap, same site name as the pure-Python runtime so one
            # fault plan drives either core (docs/fault_tolerance.md).
            _fault.fault_point("enqueue", name)
            # Payload tap: scheduled nan/corrupt mutates the tensor
            # BEFORE the guard sentinel, exercising detection end-to-end.
            tensor = _fault.payload_fault("payload", name, tensor)
        if _guard.ACTIVE and request_type in (
            RequestType.ALLREDUCE, RequestType.ADASUM
        ):
            # Non-finite sentinel, same semantics as the pure-Python
            # runtime (docs/fault_tolerance.md "Data-plane integrity").
            tensor = _guard.TAP.check_payload(name, tensor)
        entry = TensorTableEntry(
            name=name,
            tensor=tensor,
            root_rank=root_rank,
            callback=callback,
            reduce_op=reduce_op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
        )
        if _metrics.ACTIVE:
            # Metrics tap, same metric names as the pure-Python runtime
            # so dashboards are core-agnostic (docs/metrics.md).
            entry.context["metrics_enqueue_ts"] = time.monotonic()
            _metrics.TAP.inc(
                "hvd_ops_submitted_total", op=request_type.name
            )
        with self._entries_lock:
            self._entries.setdefault(name, deque()).append(entry)
        dtype = int(dtype_from_array(tensor)) if tensor is not None else 0
        shape = [int(d) for d in getattr(tensor, "shape", ())]
        try:
            ticket = self.core.enqueue(
                int(request_type), name, dtype, shape, root_rank,
                int(reduce_op), prescale_factor, postscale_factor,
                group_id, group_size, process_set_id,
            )
        except _CoreError as e:
            with self._entries_lock:
                q = self._entries.get(name)
                # The entry may already have been consumed by the
                # executor-exit drain (which fired its callback); only the
                # thread that removes it owns the completion. Identity
                # comparison — dataclass equality would compare tensor
                # payloads (ambiguous for arrays, and an equal-valued
                # sibling entry must not be confused with ours).
                idx = next(
                    (i for i, e in enumerate(q or ()) if e is entry), None
                )
                owned = idx is not None
                if owned:
                    del q[idx]
                    if not q:
                        del self._entries[name]
            status = Status(
                StatusType(e.code if 0 < e.code <= 5 else 1), str(e)
            )
            # Callback-completed consumers (TF async op kernels) wait on
            # the callback, not the handle — fire it or they hang forever
            # when the core is already down.
            if owned and entry.callback is not None:
                try:
                    entry.callback(status, None)
                except Exception:  # noqa: BLE001
                    logger.exception(
                        "error callback for %s raised", entry.name
                    )
            # Surface as a failed handle, like the reference's callback
            # error path.
            with self._cv:
                fake = -int(time.monotonic_ns() % (1 << 62)) - 1
                self._done[fake] = (status, None)
                return fake
        with self._cv:
            self._ticket_names[ticket] = name
        return ticket

    def enqueue_allreduce(self, name, tensor, **kw) -> int:
        return self._enqueue(RequestType.ALLREDUCE, name, tensor, **kw)

    def enqueue_adasum(self, name, tensor, **kw) -> int:
        kw.setdefault("reduce_op", ReduceOp.ADASUM)
        return self._enqueue(RequestType.ADASUM, name, tensor, **kw)

    def enqueue_allgather(self, name, tensor, **kw) -> int:
        return self._enqueue(RequestType.ALLGATHER, name, tensor, **kw)

    def enqueue_broadcast(self, name, tensor, root_rank, **kw) -> int:
        return self._enqueue(
            RequestType.BROADCAST, name, tensor, root_rank=root_rank, **kw
        )

    def enqueue_alltoall(self, name, tensor, **kw) -> int:
        return self._enqueue(RequestType.ALLTOALL, name, tensor, **kw)

    def enqueue_reducescatter(self, name, tensor, **kw) -> int:
        return self._enqueue(RequestType.REDUCESCATTER, name, tensor, **kw)

    def enqueue_join(self) -> int:
        if not self.running:
            from .. import HorovodInternalError

            raise HorovodInternalError("Horovod runtime is shut down.")
        return self.core.enqueue_join()

    # --- process sets (later-reference horovod.ProcessSet parity) ---
    def register_process_set(self, psid: int, ranks) -> None:
        """Register a rank subset in the native core AND the data-plane
        executor (which builds the member sub-mesh). Atomic: an executor
        failure rolls the core registration back, so control plane and
        data plane can never disagree about a set. The caller is
        responsible for the cross-rank registration barrier."""
        self.core.register_process_set(psid, list(ranks))
        reg = getattr(self.executor, "register_process_set", None)
        if reg is not None:
            try:
                reg(psid, ranks)
            except Exception:
                try:
                    self.core.remove_process_set(psid)
                except Exception:  # noqa: BLE001 - keep the original error
                    pass
                raise

    def remove_process_set(self, psid: int) -> None:
        self.core.remove_process_set(psid)
        rem = getattr(self.executor, "remove_process_set", None)
        if rem is not None:
            rem(psid)

    # --- executor loop ---
    def _executor_loop(self) -> None:
        try:
            while not self._stop.is_set() and not self._core_down.is_set():
                if self._sync_waiters > 0:
                    # A synchronize() caller is inline-draining; park so
                    # the hot thread keeps the consumer role. Bounded
                    # wait: _stop has no channel into this Event (but an
                    # inline waiter that sees the core die sets BOTH
                    # _core_down and _no_waiters to break the park).
                    self._no_waiters.wait(timeout=0.05)
                    continue
                with self._consumer_lock:
                    if self._sync_waiters > 0:
                        continue
                    plan = self.core.next_plan(timeout_ms=100)
                    if plan == -1:
                        break
                    if plan in (0, -2):
                        continue
                    self._execute_plan(plan)
        finally:
            # Core is down (peer loss, shutdown) or the loop itself died:
            # entries that never made it into a plan still hold
            # completion callbacks — e.g. TF async op kernels blocked
            # inside a tf.function train step. Fire them with an error so
            # graph-mode training surfaces the failure instead of hanging
            # forever (the handle-based waiters are failed by the core's
            # own FailAll). try/finally: an exception escaping the loop
            # must still drain, or the hang returns.
            self._drain_entry_callbacks(
                Status.Aborted(
                    "Horovod control plane is down (peer loss or "
                    "shutdown)."
                )
            )

    def _drain_entry_callbacks(self, status: Status) -> None:
        with self._entries_lock:
            orphaned = [
                e for q in self._entries.values() for e in q
            ]
            self._entries.clear()
        for entry in orphaned:
            if entry.callback is not None:
                try:
                    entry.callback(status, None)
                except Exception:  # noqa: BLE001
                    logger.exception(
                        "error callback for %s raised", entry.name
                    )
        # drain: nothing further; core fails outstanding tickets itself.

    def _execute_plan(self, plan: dict) -> None:
        t0 = time.perf_counter()
        names = plan.get("names", [])
        shapes = plan.get("shapes", [])
        entries = []
        for i, name in enumerate(names):
            with self._entries_lock:
                q = self._entries.get(name)
                entry = q.popleft() if q else None
                if q is not None and not q:
                    del self._entries[name]
            if entry is None:
                # Join zero-substitution: fabricate a zero tensor of the
                # coordinator-validated shape (reference joined-rank
                # behavior).
                shape = tuple(shapes[i]) if i < len(shapes) else ()
                np_dtype = dtype_name(DataType(plan["dtype"]))
                entry = TensorTableEntry(
                    name=name,
                    tensor=np.zeros(shape, dtype=np_dtype),
                    reduce_op=ReduceOp(plan["op"]) if plan.get("op") else ReduceOp.SUM,
                    prescale_factor=plan.get("prescale", 1.0),
                    postscale_factor=plan.get("postscale", 1.0),
                )
            entries.append(entry)

        op_label = _PLAN_TYPE_NAMES.get(int(plan["type"]), str(plan["type"]))
        if _metrics.ACTIVE:
            now = time.monotonic()
            for entry in entries:
                ts = entry.context.pop("metrics_enqueue_ts", None)
                if ts is not None:
                    _metrics.TAP.observe(
                        "hvd_op_negotiate_seconds", now - ts, op=op_label
                    )
            with self._entries_lock:
                depth = sum(len(q) for q in self._entries.values())
            _metrics.TAP.set("hvd_queue_depth", float(depth))

        status_code = 0
        error = ""
        outputs: Dict[str, Any] = {}
        if plan["type"] == _PLAN_ERROR:
            # Coordinator-detected conflict (mismatched metadata across
            # ranks, poisoned group): a named ABORT — the same status
            # class as the stall ladder — so waiters raise
            # HorovodInternalError and the elastic layer resets through
            # the usual drain instead of treating it as a local bug.
            status_code = int(StatusType.ABORTED)
            error = plan.get("error", "coordinator reported an error")
            logger.error("coordinator abort: %s", error)
            if _metrics.ACTIVE:
                _metrics.TAP.inc("hvd_guard_metadata_aborts_total")
        elif plan["type"] == _PLAN_JOIN:
            pass
        else:
            try:
                # Correlation with on-chip profiles: the same
                # "hvd_plan_<id>" string the C++ timeline stamps on this
                # plan's activity events (Timeline::BeginPlan) annotates
                # the XLA execution in any active jax.profiler trace, so
                # a slow cycle in the catapult timeline can be matched to
                # its device-side profile (SURVEY §5 timeline parity).
                import jax.profiler as _prof

                with _prof.TraceAnnotation(f"hvd_plan_{plan['id']}"):
                    outputs = self.executor.execute(
                        plan, entries, self.topology
                    )
            except Exception as exc:  # noqa: BLE001
                logger.exception("plan execution failed")
                status_code = int(StatusType.UNKNOWN_ERROR)
                error = str(exc)
        if _fault.ACTIVE and status_code == 0:
            # Output payload tap: a scheduled corrupt bit-flips THIS
            # rank's result only — the SDC model the parameter-digest
            # guard detects and heals (docs/fault_tolerance.md).
            for entry in entries:
                if entry.name in outputs:
                    outputs[entry.name] = _fault.payload_fault(
                        "output", entry.name, outputs[entry.name]
                    )
        duration = time.perf_counter() - t0
        status = (
            Status.OK()
            if status_code == 0
            else Status(StatusType(status_code), error)
        )
        if _trace.ACTIVE:
            # Fleet-trace span carrying the SAME hvd_plan_<id> string
            # the C++ timeline stamps on this plan's activity events and
            # the jax.profiler annotation above wraps its execution in —
            # one id links step → plan → collective across all three
            # artifacts (docs/timeline.md).
            _trace.TAP.event(
                "hvd_plan", ph="X", cat="plan",
                ts=time.time() - duration, dur=duration,
                plan=f"hvd_plan_{plan['id']}", op=op_label,
                tensors=len(names),
                bytes=int(plan.get("total_bytes", 0) or 0),
                ok=status_code == 0,
            )
        if _metrics.ACTIVE:
            _metrics.TAP.inc("hvd_plans_total", op=op_label)
            _metrics.TAP.observe(
                "hvd_op_execute_seconds", duration, op=op_label
            )
            nbytes = int(plan.get("total_bytes", 0) or 0)
            if nbytes:
                _metrics.TAP.observe("hvd_op_bytes", nbytes, op=op_label)
            if status_code != 0:
                _metrics.TAP.inc("hvd_op_errors_total", op=op_label)
        for entry in entries:
            out = outputs.get(entry.name)
            if entry.callback is not None:
                try:
                    entry.callback(status, out)
                except Exception:  # noqa: BLE001
                    logger.exception("callback for %s raised", entry.name)
            if status.ok():
                with self._cv:
                    self._outputs.setdefault(entry.name, deque()).append(out)
        self.core.plan_done(
            int(plan["id"]), status_code, error, duration,
            int(plan.get("total_bytes", 0)),
        )
        with self._cv:
            self._cv.notify_all()

    # --- sync helpers ---
    def poll(self, handle: int) -> bool:
        with self._cv:
            if handle in self._done:
                return True
        state, err = self.core.ticket_status(handle)
        if state == 0:
            return False
        with self._cv:
            name = self._ticket_names.pop(handle, None)
            if state == 1:
                out = None
                q = self._outputs.get(name) if name else None
                if q:
                    out = q.popleft()
                    if not q:
                        del self._outputs[name]
                self._done[handle] = (Status.OK(), out)
            else:
                code = -state
                self._done[handle] = (
                    Status(StatusType(code if 0 < code <= 5 else 1), err),
                    None,
                )
        return True

    def synchronize(self, handle: int, timeout: Optional[float] = None) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        if self._inline_sync:
            with self._cv:
                self._sync_waiters += 1
                self._no_waiters.clear()
        # This thread is now committed to waiting: anything it was going
        # to submit is already queued, so the core may seal the next
        # cycle immediately instead of holding the fusion grace for
        # companions that are not coming. Independent of the inline-sync
        # knob — a non-inline waiter is equally committed.
        if self._flush_hint:
            try:
                self.core.flush_hint()
            except Exception:  # noqa: BLE001 - hint only
                pass
        try:
            while True:
                if self.poll(handle):
                    with self._cv:
                        status, out = self._done.pop(handle)
                    if not status.ok():
                        # HorovodInternalError so elastic rollback can
                        # distinguish collective failures from user bugs.
                        from .. import HorovodInternalError

                        raise HorovodInternalError(status.reason)
                    return out
                if deadline is not None and time.monotonic() > deadline:
                    with self._cv:
                        name = self._ticket_names.get(handle, "")
                    raise TimeoutError(
                        "operation "
                        + (f"'{name}' " if name else f"handle {handle} ")
                        + f"did not complete within {timeout}s; it is "
                        "still in progress"
                    )
                # Inline fast path: consume the next plan on THIS thread
                # (see _consumer_lock comment). Non-blocking acquire —
                # another synchronize() caller may already be consuming,
                # in which case its _cv notify wakes us below.
                if (self._inline_sync
                        and self._consumer_lock.acquire(blocking=False)):
                    try:
                        if self._stop.is_set():
                            continue
                        plan = self.core.next_plan(timeout_ms=1)
                        if plan == -1:
                            # Core down. The executor thread owns the
                            # orphaned-entry callback drain
                            # (_drain_entry_callbacks); wake it out of
                            # its waiters park so callback-consumers are
                            # failed promptly instead of after every
                            # synchronize() caller exits via FailAll.
                            self._core_down.set()
                            self._no_waiters.set()
                        elif plan not in (0, -2):
                            self._execute_plan(plan)
                        continue
                    finally:
                        self._consumer_lock.release()
                with self._cv:
                    self._cv.wait(
                        timeout=0.001 if self._inline_sync else 0.01
                    )
        finally:
            if self._inline_sync:
                with self._cv:
                    self._sync_waiters -= 1
                    if self._sync_waiters == 0:
                        self._no_waiters.set()
