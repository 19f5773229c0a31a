"""horovod_tpu — a TPU-native distributed training framework with
Horovod-capability parity.

Public API parity with ``horovod/common/basics.py`` + framework modules:
``init/shutdown/size/rank/local_rank/local_size``, eager
``allreduce/allgather/broadcast`` (sync and ``_async`` handle-based variants),
``join``, ``Compression``, ``Average/Sum/Adasum`` reduce ops — plus the
TPU-native compiled mode under :mod:`horovod_tpu.jax` (fusion-bucketed psum
inside pjit/shard_map) which is the performance path.

The data plane is XLA: collectives lower to ``jax.lax.psum`` /
``all_gather`` / ``ppermute`` over ICI (intra-slice) and DCN (inter-slice)
instead of NCCL/MPI/Gloo (see SURVEY.md §5 "Distributed communication
backend").
"""

from __future__ import annotations

import atexit
import threading
from typing import Any, Optional

from .common import topology as _topology_mod
from .common.compression import Compression
from .common.env import Config
from .common.types import (
    Adasum,
    Average,
    Max,
    Min,
    Product,
    ReduceOp,
    Status,
    Sum,
)
from .core.runtime import Runtime

__version__ = "0.1.0"

_lock = threading.Lock()
_runtime: Optional[Runtime] = None
_mesh = None


class HorovodInternalError(RuntimeError):
    pass


def init(config: Optional[Config] = None) -> None:
    """Initialize the runtime (reference ``hvd.init()``,
    ``horovod/common/basics.py:33-65``): detect topology, start the
    background loop, and stand up the data plane."""
    global _runtime
    import os as _os_mod

    if _os_mod.environ.get("HOROVOD_ELASTIC_SPARE") == "1":
        # Hot-spare gate (docs/fault_tolerance.md "Self-driving
        # fleet"): a spare worker parks HERE — before any backend or
        # topology detection — until a published world generation
        # claims its slot; promotion applies the assignment env and
        # falls through into a normal init. Deliberately outside the
        # lock: the wait can last the whole job.
        from .elastic import maybe_wait_as_spare

        maybe_wait_as_spare()
    with _lock:
        if _runtime is not None and _runtime.running:
            return
        cfg = config or Config.from_env()
        # XLA perf-flag preset (docs/overlap.md): must land in LIBTPU_INIT_ARGS
        # before the first backend touch below (jax.distributed /
        # jax.devices); idempotent if horovod_tpu.jax already applied it.
        from .common import env as _env_mod

        _env_mod.apply_xla_perf_preset(cfg.xla_perf_preset)
        topo = _topology_mod.detect()
        import os as _os

        kind = _os.environ.get("HOROVOD_TPU_CORE", "native").lower()
        executor = None
        coord_addr = ""
        coord_port = 0
        if topo.size > 1:
            coord_addr = _os.environ.get("HOROVOD_CONTROLLER_ADDR", "")
            coord_port = int(_os.environ.get("HOROVOD_CONTROLLER_PORT", "0"))
            jax_coord = _os.environ.get("HOROVOD_JAX_COORDINATOR", "")
            if not coord_addr or not coord_port:
                raise HorovodInternalError(
                    f"size={topo.size} but HOROVOD_CONTROLLER_ADDR/PORT are "
                    "not set — launch multi-rank jobs with hvdrun "
                    "(python -m horovod_tpu.run)."
                )
            import jax as _jax

            if _os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
                # Multi-process CPU runs (test clusters, the launcher's
                # -np N mode) need the gloo cross-process collective
                # backend; without it every collective fails with
                # "Multiprocess computations aren't implemented on the
                # CPU backend".
                try:
                    _jax.config.update(
                        "jax_cpu_collectives_implementation", "gloo"
                    )
                except Exception:  # noqa: BLE001 - newer jax: on by default
                    pass

            if jax_coord:
                # Must run before any backend use; tolerate re-init.
                from .elastic import rejoin_mode as _rejoin_mode

                if (_os.environ.get("HOROVOD_ELASTIC") == "1"
                        and _rejoin_mode() == "inprocess"):
                    # Elastic worlds need failure-tolerant coordination: a
                    # dead peer must surface as a catchable collective
                    # error on survivors, not a fatal coordination-service
                    # abort — rollback re-forms the world in process
                    # (horovod_tpu/elastic). In 'respawn' mode (the
                    # fallback when these private surfaces are absent)
                    # workers die and resume from persisted commits, so
                    # the plain public initialize below is used instead.
                    _jax.config.update("jax_enable_recoverability", True)
                    from .elastic import _jax_distributed_initialize

                    def _dist_init():
                        _jax_distributed_initialize(
                            jax_coord, topo.size, topo.rank
                        )
                else:
                    def _dist_init():
                        _jax.distributed.initialize(
                            jax_coord, num_processes=topo.size,
                            process_id=topo.rank,
                        )
                try:
                    _dist_init()
                except RuntimeError as exc:
                    msg = str(exc).lower()
                    if "already" in msg:
                        pass
                    elif (_os.environ.get("HOROVOD_ELASTIC") == "1"
                          and _rejoin_mode() == "respawn"
                          and any(k in msg for k in (
                              "bind", "address already in use",
                              "address in use", "errno 98",
                              "failed to listen"))):
                        # The coordinator port was probed on the driver
                        # host (or a remote probe fell back) and lost the
                        # bind race here. Not this host's fault: exit
                        # with the respawn status so the driver re-forms
                        # the world with FRESH ports and records no
                        # blacklist strike, instead of burning one of the
                        # host's failure credits per collision.
                        import logging as _logging

                        _logging.getLogger("horovod_tpu").error(
                            "jax coordination endpoint could not bind "
                            "(%s); exiting for a respawn with fresh "
                            "ports", exc,
                        )
                        from .elastic import REJOIN_EXIT_CODE

                        _os._exit(REJOIN_EXIT_CODE)
                    else:
                        raise
            from .core.xla_executor import XlaPlanExecutor

            executor = XlaPlanExecutor(topo, config=cfg)
        if kind == "native":
            # A core that cannot be built from cpp/src or loaded raises
            # here. The pure-Python runtime is HOROVOD_TPU_CORE=python's
            # to choose, never a quiet second try.
            from .core.native_runtime import NativeRuntime

            _runtime = NativeRuntime(
                cfg, topo, executor=executor,
                coord_addr=coord_addr, coord_port=coord_port,
            )
            _start_profiler(cfg)
            _start_metrics_pusher(topo)
            _start_trace_pusher(topo)
            return
        _runtime = Runtime(cfg, topo)
        _runtime.start()
        _start_profiler(cfg)
        _start_metrics_pusher(topo)
        _start_trace_pusher(topo)


def _start_profiler(cfg: Config) -> None:
    """Optional jax.profiler session (HOROVOD_PROFILER_DIR): plan
    executions carry the same hvd_plan_<id> TraceAnnotation the C++
    timeline stamps on the plan's catapult events, linking a slow cycle
    to its on-chip XLA profile (SURVEY §5 timeline parity)."""
    global _profiler_active
    if not getattr(cfg, "profiler_dir", ""):
        return
    try:
        import jax.profiler as _prof

        _prof.start_trace(cfg.profiler_dir)
        _profiler_active = True
    except Exception as exc:  # noqa: BLE001 - profiling is best-effort
        import logging

        logging.getLogger("horovod_tpu").warning(
            "could not start jax.profiler trace in %s: %s",
            cfg.profiler_dir, exc,
        )


_profiler_active = False
_metrics_pusher = None
_trace_pusher = None


def _start_trace_pusher(topo) -> None:
    """Worker-side fleet-trace publisher (docs/timeline.md "Fleet
    tracing"): with HOROVOD_TRACE set and an elastic KV rendezvous in
    the environment, estimate the clock offset against the driver (KV
    ping RTT/2, recorded as trace metadata) and push this rank's span
    window so the driver can merge the fleet. No-op otherwise."""
    global _trace_pusher
    from . import trace as _trace_mod

    if not _trace_mod.ACTIVE:
        return
    # The tap armed at import, possibly before this generation's rank
    # assignment landed in the env — adopt the live rank (an in-process
    # rejoin re-enters here after shutdown() stopped the old pusher).
    _trace_mod.TAP.rank = topo.rank
    if _trace_pusher is not None:
        return
    import os as _os

    addr = _os.environ.get("HOROVOD_ELASTIC_KV_ADDR", "")
    port = _os.environ.get("HOROVOD_ELASTIC_KV_PORT", "")
    if not addr or not port:
        return
    from .trace.pusher import TracePusher

    try:
        _trace_pusher = TracePusher(addr, int(port), topo.rank)
    except Exception as exc:  # noqa: BLE001 - tracing never blocks init
        import logging

        logging.getLogger("horovod_tpu").warning(
            "could not start the trace pusher: %s", exc
        )


def _start_metrics_pusher(topo) -> None:
    """Worker-side metrics publisher (docs/metrics.md): with
    HOROVOD_METRICS set and an elastic KV rendezvous in the environment,
    push this process's registry snapshot to the driver so its
    ``GET /metrics`` aggregates every rank. No-op otherwise — the
    in-process ``hvd.metrics()`` API needs no plumbing."""
    global _metrics_pusher
    from . import metrics as _metrics_mod

    if not _metrics_mod.ACTIVE or _metrics_pusher is not None:
        return
    import os as _os

    addr = _os.environ.get("HOROVOD_ELASTIC_KV_ADDR", "")
    port = _os.environ.get("HOROVOD_ELASTIC_KV_PORT", "")
    if not addr or not port:
        return
    from .metrics.export import MetricsPusher

    try:
        _metrics_pusher = MetricsPusher(addr, int(port), topo.rank)
    except Exception as exc:  # noqa: BLE001 - metrics never block init
        import logging

        logging.getLogger("horovod_tpu").warning(
            "could not start the metrics pusher: %s", exc
        )


def metrics_snapshot() -> dict:
    """Structured snapshot of this process's metrics registry — plain
    dicts/lists/numbers only (counters, gauges, and fixed-bucket
    histograms; see docs/metrics.md). Empty when ``HOROVOD_METRICS`` is
    unset. ``hvd.metrics()`` returns the flattened one-value-per-series
    view of the same data."""
    from . import metrics as _metrics_mod

    return _metrics_mod.snapshot()


def shutdown() -> None:
    global _runtime, _mesh, _profiler_active, _ps_barrier_seq
    global _metrics_pusher, _trace_pusher
    with _lock:
        if _runtime is not None:
            _runtime.shutdown()
            _runtime = None
        if _trace_pusher is not None:
            # Stopped AFTER the runtime so the final window carries the
            # teardown-time spans (same ordering as the metrics pusher).
            try:
                _trace_pusher.stop()
            except Exception:  # noqa: BLE001
                pass
            _trace_pusher = None
        if _metrics_pusher is not None:
            # Stopped AFTER the runtime so the final push carries the
            # teardown-time counter values.
            try:
                _metrics_pusher.stop()
            except Exception:  # noqa: BLE001
                pass
            _metrics_pusher = None
        _mesh = None
        # Process sets die with the runtime (a re-init starts clean, and
        # id assignment restarts so all ranks stay aligned).
        for ps in _process_sets.values():
            ps.process_set_id = None
        _process_sets.clear()
        _ps_barrier_seq = 0
        if _profiler_active:
            _profiler_active = False
            try:
                import jax.profiler as _prof

                _prof.stop_trace()
            except Exception:  # noqa: BLE001
                pass


def is_initialized() -> bool:
    return _runtime is not None and _runtime.running


def _rt() -> Runtime:
    if _runtime is None or not _runtime.running:
        raise HorovodInternalError(
            "Horovod has not been initialized; use hvd.init()."
        )
    return _runtime


atexit.register(shutdown)


# --- topology accessors (basics.py parity) ---
def size() -> int:
    return _rt().topology.size


def rank() -> int:
    return _rt().topology.rank


def local_rank() -> int:
    return _rt().topology.local_rank


def local_size() -> int:
    return _rt().topology.local_size


def cross_rank() -> int:
    return _rt().topology.cross_rank


def cross_size() -> int:
    return _rt().topology.cross_size


def is_homogeneous() -> bool:
    return _rt().topology.is_homogeneous


def collective_plan(
    collective: str = "allreduce",
    nbytes: int = 4 * 1024 * 1024,
    op: Optional[ReduceOp] = None,
    wire_dtype: str = "f32",
) -> dict:
    """The topology compositor's selected lowering plan for one
    collective at one payload size on THIS deployment's interconnect
    model (docs/topology.md): algorithm (flat / ring / recursive-halving
    / two-level / split), per-hop bytes-on-wire, per-stage schedule, and
    the analytic cost estimate. ``wire_dtype="int8"`` prices the
    quantized wire (allreduce SUM/AVERAGE only): int8+scales bytes on
    the compressed hop(s), full precision elsewhere. Uses the
    initialized runtime's topology when available, else fresh detection;
    honors the ``HOROVOD_TOPOLOGY_MODEL`` override. Pure cost-model
    output — no backend is touched, so this also works pre-init (the
    offline twin is ``tools/topo_plan.py``)."""
    from .topo import resolve_model, select_plan

    topo = (
        _runtime.topology if _runtime is not None
        else _topology_mod.detect()
    )
    model = resolve_model(topo)
    plan = select_plan(
        model, collective, int(nbytes),
        op=op if op is not None else ReduceOp.SUM,
        wire_dtype=wire_dtype,
    )
    out = plan.to_dict()
    out["model"] = model.to_dict()
    return out


# Build-capability probes (reference horovod_*_built/enabled,
# operations.cc:683-769). MPI/Gloo/NCCL/DDL/MLSL do not exist in the TPU
# build; XLA is the sole data plane.
def mpi_threads_supported() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def mlsl_built() -> bool:
    return False


def xla_built() -> bool:
    return True


def xla_enabled() -> bool:
    return True


def mesh():
    """The global device mesh (lazily built; single ``data`` axis over all
    devices by default, or per ``HOROVOD_TPU_MESH_AXES``)."""
    global _mesh
    with _lock:
        if _mesh is None:
            from .parallel import mesh as mesh_mod

            cfg = _rt().config
            _mesh = mesh_mod.build_mesh(mesh_mod.parse_axes(cfg.mesh_axes) or None)
        return _mesh


# --- naming helper ---
_name_counters: dict = {}


def _auto_name(prefix: str, name: Optional[str]) -> str:
    if name is not None:
        return name
    with _lock:
        n = _name_counters.get(prefix, 0)
        _name_counters[prefix] = n + 1
    return f"{prefix}.noname.{n}"


def _preflight_record(op: str, name: str, psid: int, tensor: Any) -> None:
    """Opt-in submission-ledger hook (HOROVOD_TPU_STATIC_CHECKS=1): feeds
    the cross-rank ordering lint (analysis/ordering.py). No-op — a single
    cached env read — when the knob is off."""
    from .analysis import preflight

    if preflight.enabled():
        preflight.record_submission(op, name, psid, tensor)


def _resolve_op(average: Optional[bool], op: Optional[ReduceOp]) -> ReduceOp:
    # Reference horovod/torch/mpi_ops.py:101-124: `average` and `op` are
    # mutually exclusive; default Average.
    if average is not None and op is not None:
        raise ValueError('The op parameter supersedes average; provide only one.')
    if op is not None:
        return op
    if average is False:
        return ReduceOp.SUM
    return ReduceOp.AVERAGE


# --- process sets (later-reference horovod.ProcessSet parity) ---
class ProcessSet:
    """A subset of ranks that collectives can run over (the later
    reference's ``horovod.ProcessSet``). TPU-native design: a registered
    set becomes a sub-``Mesh`` over the member ranks' devices — only
    member processes execute the compiled collective (multi-controller
    JAX semantics), which is exactly the reference's per-set communicator
    without a NCCL/MPI comm split.

    Construct with a list of global ranks and register with
    :func:`add_process_set` (which must be called identically on every
    rank); ``hvd.global_process_set`` is the implicit all-ranks set."""

    def __init__(self, ranks=None):
        # None = the global set (all ranks, resolved at use time).
        self.ranks = (
            sorted({int(r) for r in ranks}) if ranks is not None else None
        )
        self.process_set_id: Optional[int] = None

    def _resolved_ranks(self) -> list:
        return self.ranks if self.ranks is not None else list(range(size()))

    def size(self) -> int:
        return len(self._resolved_ranks())

    def included(self) -> bool:
        return rank() in self._resolved_ranks()

    def rank(self) -> int:
        """This process's position within the set (set-local rank)."""
        rs = self._resolved_ranks()
        me = rank()
        if me not in rs:
            raise RuntimeError(
                f"rank {me} is not a member of process set "
                f"{self.process_set_id}"
            )
        return rs.index(me)

    def __repr__(self):
        return (f"ProcessSet(id={self.process_set_id}, "
                f"ranks={'GLOBAL' if self.ranks is None else self.ranks})")


global_process_set = ProcessSet(None)
global_process_set.process_set_id = 0

_process_sets: dict = {}
# Per-call barrier sequence, shared by add_process_set AND
# remove_process_set: the k-th registration call uses barrier name k and
# (for adds) set id k on EVERY rank — even ranks whose local validation
# failed, and even when one rank is adding while another removes — so any
# divergent call completes the allgather and fails loudly on all ranks
# instead of stranding the healthy ones inside it, and a failed call can
# never desynchronize id assignment (all ranks consumed the same value).
_ps_barrier_seq = 0


def _ps_barrier(payload, seq: int, n: int) -> list:
    """Cross-rank agreement exchange for process-set registration calls.
    ONE name per sequence number regardless of call type — an add on one
    rank racing a remove on another meets in the same allgather and the
    payload mismatch raises everywhere."""
    if n <= 1:
        return [payload]
    return allgather_object(payload, name=f"hvd.ps.bar.{seq}")


def _psid(process_set: Optional[ProcessSet]) -> int:
    if process_set is None or process_set.process_set_id == 0:
        return 0
    if process_set.process_set_id is None:
        raise ValueError(
            "process set must be registered with hvd.add_process_set() "
            "before use"
        )
    return int(process_set.process_set_id)


def add_process_set(process_set) -> ProcessSet:
    """Register a process set (a ``ProcessSet`` or a list of ranks).
    MUST be called identically, in the same order, on every rank — the
    registration performs a cross-rank agreement barrier so a divergent
    call (wrong ranks on one rank, different membership across ranks)
    fails loudly on EVERY rank instead of deadlocking the first
    collective: local validation failures enter the barrier too and
    poison it."""
    global _ps_barrier_seq
    ps = (process_set if isinstance(process_set, ProcessSet)
          else ProcessSet(process_set))
    rt = _rt()
    n = rt.topology.size
    with _lock:
        _ps_barrier_seq += 1
        seq = _ps_barrier_seq
    # Validate into an error payload rather than raising before the
    # barrier — a pre-barrier raise would strand every healthy peer
    # inside the agreement allgather.
    err = None
    if ps.ranks is None:
        err = "the global process set is registered implicitly"
    elif ps.process_set_id is not None:
        err = f"process set is already registered (id {ps.process_set_id})"
    elif not ps.ranks or ps.ranks[0] < 0 or ps.ranks[-1] >= n:
        err = f"process set ranks must lie in [0, {n})"
    psid = None
    if err is None:
        reg = getattr(rt, "register_process_set", None)
        if reg is None:
            err = "the active runtime does not support process sets"
        else:
            # The set id IS the barrier sequence number: consumed
            # identically on every rank by every registration call,
            # successful or not, so a failed call can never skew later
            # id assignment across ranks.
            psid = seq
            try:
                # Register BEFORE the barrier: a member may use the set
                # the moment its own barrier returns, which implies every
                # rank (the coordinator included) contributed — and hence
                # registered — already.
                reg(psid, ps.ranks)
            except Exception as exc:  # noqa: BLE001 - poisons the barrier
                err = str(exc)
                psid = None
    payload = (("add", psid, tuple(ps.ranks or ()))
               if err is None else ("err", err))
    agreement = _ps_barrier(payload, seq, n)
    unanimous = len(set(agreement)) == 1 and agreement[0][0] == "add"
    if err is not None or not unanimous:
        if psid is not None:
            try:
                rt.remove_process_set(psid)
            except Exception:  # noqa: BLE001 - best-effort rollback
                pass
        if err is not None:
            raise ValueError(err)
        raise ValueError(
            "add_process_set must be called identically on every rank; "
            f"cross-rank registrations: {agreement}"
        )
    with _lock:
        ps.process_set_id = psid
        _process_sets[psid] = ps
    return ps


def remove_process_set(process_set: ProcessSet) -> None:
    """Deregister a dynamic process set. Collective: call identically on
    every rank (barrier first, so no member removes the set while a peer
    still has ops in flight; a divergent call fails on all ranks)."""
    global _ps_barrier_seq
    rt = _rt()
    n = rt.topology.size
    with _lock:
        _ps_barrier_seq += 1
        seq = _ps_barrier_seq
    psid = process_set.process_set_id
    err = (
        "only registered non-global process sets can be removed"
        if psid in (None, 0) else None
    )
    payload = ("rm", psid) if err is None else ("err", err)
    agreement = _ps_barrier(payload, seq, n)
    if err is not None:
        raise ValueError(err)
    if any(a != ("rm", psid) for a in agreement):
        raise ValueError(
            "remove_process_set must be called identically on every "
            f"rank; cross-rank calls: {agreement}"
        )
    rt.remove_process_set(psid)
    with _lock:
        _process_sets.pop(psid, None)
        process_set.process_set_id = None


# --- eager collective API ---
def allreduce_async(
    tensor: Any,
    average: Optional[bool] = None,
    name: Optional[str] = None,
    op: Optional[ReduceOp] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    _group: tuple = (0, 0),
) -> int:
    rop = _resolve_op(average, op)
    rt = _rt()
    tensor_name = _auto_name("allreduce", name)
    psid = _psid(process_set)
    _preflight_record("allreduce", tensor_name, psid, tensor)
    if rop == ReduceOp.ADASUM:
        return rt.enqueue_adasum(
            tensor_name,
            tensor,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            group_id=_group[0], group_size=_group[1],
            process_set_id=psid,
        )
    return rt.enqueue_allreduce(
        tensor_name,
        tensor,
        reduce_op=rop,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        group_id=_group[0], group_size=_group[1],
        process_set_id=psid,
    )


def allreduce(
    tensor: Any,
    average: Optional[bool] = None,
    name: Optional[str] = None,
    compression=Compression.none,
    op: Optional[ReduceOp] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
) -> Any:
    tensor_compressed, ctx = compression.compress(tensor)
    handle = allreduce_async(
        tensor_compressed,
        average=average,
        name=name,
        op=op,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        process_set=process_set,
    )
    out = synchronize(handle)
    return compression.decompress(out, ctx)


def allgather_async(tensor: Any, name: Optional[str] = None,
                    process_set: Optional[ProcessSet] = None,
                    _group: tuple = (0, 0)) -> int:
    tensor_name = _auto_name("allgather", name)
    psid = _psid(process_set)
    _preflight_record("allgather", tensor_name, psid, tensor)
    return _rt().enqueue_allgather(
        tensor_name, tensor,
        process_set_id=psid,
        group_id=_group[0], group_size=_group[1],
    )


def allgather(tensor: Any, name: Optional[str] = None,
              process_set: Optional[ProcessSet] = None) -> Any:
    return synchronize(allgather_async(tensor, name, process_set))


def allgather_object(obj, name: Optional[str] = None,
                     process_set: Optional[ProcessSet] = None) -> list:
    """Gather one picklable object per (member) rank; every member gets
    the member-ordered list (later-reference API). Rides the uneven
    (Allgatherv-parity) dim0 allgather, so payload sizes may differ."""
    import pickle

    import numpy as np

    data = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    base = name or _auto_name("gather_obj", None)
    sizes = np.asarray(allgather(
        np.array([len(data)], dtype=np.int64), name=f"{base}.size",
        process_set=process_set,
    ))
    payload = np.asarray(allgather(
        data, name=f"{base}.data", process_set=process_set,
    ))
    out, off = [], 0
    for count in sizes.tolist():
        out.append(pickle.loads(payload[off:off + count].tobytes()))
        off += count
    return out


def broadcast_async(
    tensor: Any, root_rank: int, name: Optional[str] = None,
    process_set: Optional[ProcessSet] = None,
) -> int:
    # root_rank is a GLOBAL rank even within a process set (reference
    # process-set API semantics; the executor maps it to the member
    # position on the sub-mesh).
    tensor_name = _auto_name("broadcast", name)
    psid = _psid(process_set)
    _preflight_record("broadcast", tensor_name, psid, tensor)
    return _rt().enqueue_broadcast(
        tensor_name, tensor, root_rank,
        process_set_id=psid,
    )


def broadcast(tensor: Any, root_rank: int, name: Optional[str] = None,
              process_set: Optional[ProcessSet] = None) -> Any:
    return synchronize(broadcast_async(tensor, root_rank, name, process_set))


def alltoall_async(tensor: Any, name: Optional[str] = None,
                   process_set: Optional[ProcessSet] = None) -> int:
    tensor_name = _auto_name("alltoall", name)
    psid = _psid(process_set)
    _preflight_record("alltoall", tensor_name, psid, tensor)
    return _rt().enqueue_alltoall(
        tensor_name, tensor,
        process_set_id=psid,
    )


def alltoall(tensor: Any, splits: Any = None, name: Optional[str] = None,
             process_set: Optional[ProcessSet] = None) -> Any:
    """All-to-all scatter of dim0 blocks. Without ``splits``, dim0 must
    divide evenly by the set size and rank r receives block r from every
    rank. With ``splits`` (length ``size``, summing to dim0 — the later
    reference's alltoallv API, ``horovod.alltoall(tensor, splits)``),
    rank d receives the ``splits[d]``-row segment from every rank and the
    call returns ``(collected, received_splits)``.

    Uneven mechanics (MPI alltoallv re-expressed on the even TPU
    collective): a tiny allgather shares every rank's splits vector, each
    per-destination segment pads to a common block, an even
    ``lax.all_to_all`` moves the blocks, and the pads are sliced off —
    the same count-exchange + v-call shape MPI implementations use.

    Memory bound under skew: padding every block to the global max would
    allocate ``O(n * max_split)`` rows on EVERY rank — one hot
    destination (an EP router's overloaded expert) would blow the
    carrier up n-fold. Instead the exchange is chunked: the carrier is
    capped at ``k * total_rows / n`` rows (``k`` =
    ``HOROVOD_ALLTOALLV_CARRIER_FACTOR``, default 4; floor ``n`` rows)
    and hot blocks ride multiple rounds. Peak extra memory is
    ``O(max(n, k * total_rows / n))`` rows regardless of skew; balanced
    splits stay single-round (``k x mean >= max``), identical to the
    unchunked path. Rounds are derived from the globally-agreed count
    matrix, so every rank executes the same schedule."""
    if splits is None:
        return synchronize(alltoall_async(tensor, name, process_set))
    import numpy as np

    name = _auto_name("alltoall", name)
    rt = _rt()
    if process_set is not None and process_set.ranks is not None:
        n = process_set.size()
        me = process_set.rank()
    else:
        n = rt.topology.size
        me = rt.topology.rank
    splits = np.asarray(splits, np.int32).reshape(-1)
    local = np.asarray(tensor)
    if splits.shape[0] != n:
        raise ValueError(
            f"splits must have one entry per rank ({n}), got "
            f"{splits.shape[0]}"
        )
    if (splits < 0).any():
        raise ValueError(f"splits must be non-negative, got {splits.tolist()}")
    if int(splits.sum()) != int(local.shape[0]):
        raise ValueError(
            f"splits sum ({int(splits.sum())}) must equal dim0 "
            f"({int(local.shape[0])})"
        )
    # Count exchange: matrix[src, dst] = rows src sends to dst.
    matrix = np.asarray(
        allgather(splits, name=f"{name}.splits", process_set=process_set)
    ).reshape(n, n)
    received_splits = matrix[:, me].copy()
    max_block = int(matrix.max())
    if max_block == 0:
        empty = local[:0]
        return empty, received_splits
    chunk, rounds = _alltoallv_schedule(matrix, n)
    alltoall._last_carrier_rows = n * chunk  # test/diagnostic hook
    rest = local.shape[1:]
    offs = np.concatenate([[0], np.cumsum(splits)[:-1]])
    pieces: list = [[] for _ in range(n)]
    for r in range(rounds):
        lo = r * chunk
        padded = np.zeros((n * chunk,) + rest, local.dtype)
        for d in range(n):
            take = min(max(int(splits[d]) - lo, 0), chunk)
            if take:
                padded[d * chunk: d * chunk + take] = (
                    local[offs[d] + lo: offs[d] + lo + take]
                )
        round_name = f"{name}.round{r}" if rounds > 1 else name
        out = np.asarray(
            synchronize(alltoall_async(padded, round_name, process_set))
        )
        for s in range(n):
            take = min(max(int(received_splits[s]) - lo, 0), chunk)
            if take:
                pieces[s].append(out[s * chunk: s * chunk + take])
    collected = np.concatenate(
        [c for p in pieces for c in p]
    ) if received_splits.sum() else local[:0]
    return collected, received_splits


def _alltoallv_schedule(matrix: Any, n: int) -> tuple:
    """(chunk_rows, rounds) for the chunked uneven alltoall: carrier
    capped at ``factor * total_rows / n`` rows (floor ``n``) so a skewed
    split cannot allocate ``n * max_split`` on every rank."""
    import os

    import numpy as np

    m = np.asarray(matrix)
    max_block = int(m.max())
    factor = int(os.environ.get("HOROVOD_ALLTOALLV_CARRIER_FACTOR", "4"))
    cap = max(1, (factor * int(m.sum()) + n * n - 1) // (n * n))
    chunk = min(max_block, cap)
    rounds = (max_block + chunk - 1) // chunk
    return chunk, rounds


def reducescatter_async(
    tensor: Any, name: Optional[str] = None, op: Optional[ReduceOp] = None,
    process_set: Optional[ProcessSet] = None,
    _group: tuple = (0, 0),
) -> int:
    """Sum/average across ranks, scatter dim0 shards: rank r receives its
    dim0 shard of the reduction — ``d//size`` rows each when ``size``
    divides ``d``, and Allgatherv-parity uneven splits otherwise (rank r
    gets ``d//size + (1 if r < d%size else 0)`` rows, earlier ranks
    absorbing the remainder — the MPI_Reduce_scatter convention the
    later reference adopted). TPU-native extension (single
    ``lax.psum_scatter`` on the ICI ring, uneven dim0 via a static
    pad-gather sliced off after the collective); the reference op set
    stops at broadcast (``message.h:48-50``)."""
    op = op if op is not None else ReduceOp.SUM
    # Validate here, not only in the multi-rank executor, so a size-1 dev
    # run rejects exactly what a production job would.
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("reducescatter supports SUM/AVERAGE only")
    if not getattr(tensor, "shape", ()):
        raise ValueError("reducescatter needs a tensor with a dim0 to scatter")
    tensor_name = _auto_name("reducescatter", name)
    psid = _psid(process_set)
    _preflight_record("reducescatter", tensor_name, psid, tensor)
    return _rt().enqueue_reducescatter(
        tensor_name, tensor, reduce_op=op,
        process_set_id=psid,
        group_id=_group[0], group_size=_group[1],
    )


def reducescatter(
    tensor: Any, name: Optional[str] = None, op: Optional[ReduceOp] = None,
    process_set: Optional[ProcessSet] = None,
) -> Any:
    return synchronize(reducescatter_async(tensor, name, op, process_set))


def grouped_allreduce_async(
    tensors, average: Optional[bool] = None, name: Optional[str] = None,
    op: Optional[ReduceOp] = None,
    prescale_factor: float = 1.0, postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
):
    """Enqueue a list of tensors as ONE first-class group and return
    their handles. The group travels with the requests (a stable id +
    member count), and the coordinator holds members until every one is
    ready on every rank, then fuses them into a single collective
    regardless of cycle boundaries or the fusion threshold — the
    semantics of the later reference's grouped API, not best-effort
    cycle fusion. Members with heterogeneous dtypes/signatures execute
    as one plan per signature (observable via the core's
    grouped_splits counter).

    If an enqueue fails partway on THIS rank, the already-submitted
    members are synchronized before re-raising; peer ranks that
    submitted the full group see the incomplete group as stalled (the
    stall inspector warns and can shut the job down) — validate inputs
    before submission when cross-rank failure atomicity matters."""
    base = name if name is not None else _auto_name("grouped_allreduce", None)
    return _grouped_async(
        lambda t, n, g: allreduce_async(
            t, average=average, name=n, op=op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            process_set=process_set, _group=g,
        ),
        tensors, base,
    )


def _grouped_async(enqueue_one, tensors, base, validate_one=None) -> list:
    """Shared grouped-submission shape (the later reference's grouped
    APIs): every member carries the group id + count, so the coordinator
    HOLDS the group until all members are ready on all ranks — members
    complete together (one per-member plan; only allreduce groups
    additionally fuse into a single buffer). Every member is validated
    BEFORE any is enqueued: a mid-group failure would leave peers
    holding a never-completable group (see ``_drain_group``)."""
    from .common.types import dtype_from_array

    tensors = list(tensors)
    for t in tensors:
        dtype_from_array(t)
        if validate_one is not None:
            validate_one(t)
    from .analysis import preflight as _preflight

    if _preflight.enabled():
        # Static group lint BEFORE any member is enqueued: a group that
        # can never fuse as one collective (mixed dtypes) or that blows
        # the fusion-buffer budget is reported here instead of stranding
        # peers holding an incomplete group.
        _preflight.check_grouped(
            tensors, _rt().config.fusion_threshold_bytes, base
        )
    gid = _group_id(base)
    handles = []
    try:
        for i, t in enumerate(tensors):
            handles.append(
                enqueue_one(t, f"{base}.{i}", (gid, len(tensors)))
            )
    except Exception:
        _drain_group(handles)
        raise
    return handles


def grouped_allgather_async(tensors, name: Optional[str] = None,
                            process_set: Optional[ProcessSet] = None):
    """Allgather a list of tensors as ONE group: the coordinator holds
    the members until every one is ready on every rank, so they complete
    atomically (later-reference ``grouped_allgather``)."""
    base = name if name is not None else _auto_name("grouped_allgather", None)
    return _grouped_async(
        lambda t, n, g: allgather_async(t, n, process_set, _group=g),
        tensors, base,
    )


def grouped_allgather(tensors, name: Optional[str] = None,
                      process_set: Optional[ProcessSet] = None):
    return grouped_sync_first_error(
        grouped_allgather_async(tensors, name, process_set), synchronize
    )


def grouped_reducescatter_async(tensors, name: Optional[str] = None,
                                op: Optional[ReduceOp] = None,
                                process_set: Optional[ProcessSet] = None):
    """Reduce-scatter a list of tensors as ONE group (atomic completion;
    later-reference ``grouped_reducescatter``)."""
    base = (name if name is not None
            else _auto_name("grouped_reducescatter", None))
    rs_op = op if op is not None else ReduceOp.SUM
    if rs_op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("reducescatter supports SUM/AVERAGE only")

    def validate_one(t):
        if not getattr(t, "shape", ()):
            raise ValueError(
                "reducescatter needs a tensor with a dim0 to scatter"
            )

    return _grouped_async(
        lambda t, n, g: reducescatter_async(t, n, op, process_set,
                                            _group=g),
        tensors, base, validate_one=validate_one,
    )


def grouped_reducescatter(tensors, name: Optional[str] = None,
                          op: Optional[ReduceOp] = None,
                          process_set: Optional[ProcessSet] = None):
    return grouped_sync_first_error(
        grouped_reducescatter_async(tensors, name, op, process_set),
        synchronize,
    )


def _group_id(base: str) -> int:
    """Cross-rank-stable nonzero group id derived from the base name
    (every rank traces the same name sequence; md5 makes collisions
    between distinct concurrent groups negligible). Masked to 63 bits:
    the id travels through signed-int64 channels (the TF custom op's
    int attr, the wire codec), where the top bit would overflow."""
    import hashlib

    raw = int.from_bytes(hashlib.md5(base.encode()).digest()[:8], "little")
    return (raw & ((1 << 63) - 1)) or 1


def _drain_group(handles) -> None:
    """Best-effort bounded wait on already-submitted group members after
    a mid-group enqueue failure. The group can never complete (the
    coordinator holds it until every member arrives), so an unbounded
    synchronize would deadlock here — wait briefly, then abandon; the
    stall inspector reports the orphaned members and peers recover via
    its warning/shutdown path."""
    for h in handles:
        try:
            _rt().synchronize(h, timeout=1.0)
        except Exception:  # noqa: BLE001 - surfacing the original error
            pass


def grouped_sync_first_error(handles, synchronize_fn):
    """Wait on every handle even when one fails (no orphaned results in
    the handle table); re-raise the first error. Shared by the top-level
    and framework grouped APIs."""
    outputs, first_error = [], None
    for h in handles:
        try:
            outputs.append(synchronize_fn(h))
        except Exception as exc:  # noqa: BLE001 - re-raised below
            if first_error is None:
                first_error = exc
    if first_error is not None:
        raise first_error
    return outputs


def grouped_allreduce(
    tensors, average: Optional[bool] = None, name: Optional[str] = None,
    op: Optional[ReduceOp] = None,
    prescale_factor: float = 1.0, postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
):
    """Synchronous :func:`grouped_allreduce_async`; returns outputs in
    input order. Every handle is waited on even when one fails, so no
    results are orphaned in the handle table; the first error wins."""
    handles = grouped_allreduce_async(
        tensors, average=average, name=name, op=op,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        process_set=process_set,
    )
    return grouped_sync_first_error(handles, synchronize)


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Start the catapult timeline at runtime (later-reference
    ``hvd.start_timeline``): same trace the ``HOROVOD_TIMELINE`` env var
    produces, but scoped to the interesting window of a long run."""
    _rt().start_timeline(file_path, mark_cycles)


def stop_timeline() -> None:
    """Stop a runtime-started timeline (later-reference API)."""
    _rt().stop_timeline()


def join() -> None:
    """Signal this rank is out of data; blocks until all ranks join
    (reference ``hvd.join``, ``operations.cc:910-934``)."""
    synchronize(_rt().enqueue_join())


def barrier(name: Optional[str] = None,
            process_set: Optional[ProcessSet] = None) -> None:
    """Block until every member rank reaches the barrier (the later
    reference's ``hvd.barrier``): expressed as a one-element allreduce,
    whose negotiate-then-execute protocol IS a barrier."""
    import numpy as np

    allreduce(
        np.zeros((1,), np.float32), op=ReduceOp.SUM,
        name=_auto_name("barrier", name), process_set=process_set,
    )


def poll(handle: int) -> bool:
    return _rt().poll(handle)


def synchronize(handle: int, timeout: Optional[float] = None) -> Any:
    return _rt().synchronize(handle, timeout)


def broadcast_object(obj: Any, root_rank: int = 0,
                     name: Optional[str] = None,
                     process_set: Optional[ProcessSet] = None) -> Any:
    """Broadcast an arbitrary picklable object from root (later-reference
    API): a size broadcast then a uint8 payload broadcast — O(payload)
    per rank, unlike an object allgather's O(size × payload)."""
    import pickle

    import numpy as np

    name = name or _auto_name("bcast_obj", None)
    # root_rank is a GLOBAL rank (same convention as broadcast, which
    # maps it to the member position on a process set).
    if rank() == root_rank:
        data = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    else:
        data = np.zeros((0,), np.uint8)
    sz = np.asarray([data.shape[0]], np.int64)
    sz = np.asarray(broadcast(sz, root_rank, name=f"{name}.size",
                              process_set=process_set))
    payload = (data if data.shape[0] == int(sz[0])
               else np.zeros(int(sz[0]), np.uint8))
    payload = np.asarray(broadcast(payload, root_rank,
                                   name=f"{name}.data",
                                   process_set=process_set))
    return pickle.loads(payload.tobytes())


def broadcast_variables(variables: Any, root_rank: int = 0) -> Any:
    """Broadcast a pytree of arrays from root (reference
    ``broadcast_variables`` / ``broadcast_parameters``). All leaves are
    enqueued async first so one negotiation cycle can fuse them into a
    single plan — latency scales with payload, not leaf count."""
    import jax

    leaves, treedef = jax.tree.flatten(variables)
    handles = [
        broadcast_async(leaf, root_rank, name=f"bcast.var.{i}")
        for i, leaf in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, [synchronize(h) for h in handles])


__all__ = [
    "init",
    "shutdown",
    "is_initialized",
    "size",
    "rank",
    "local_rank",
    "local_size",
    "cross_rank",
    "cross_size",
    "is_homogeneous",
    "mesh",
    "allreduce",
    "allreduce_async",
    "allgather",
    "allgather_async",
    "broadcast",
    "broadcast_async",
    "alltoall",
    "alltoall_async",
    "reducescatter",
    "reducescatter_async",
    "grouped_allreduce",
    "grouped_allreduce_async",
    "allgather_object",
    "broadcast_object",
    "ProcessSet",
    "global_process_set",
    "add_process_set",
    "remove_process_set",
    "join",
    "barrier",
    "start_timeline",
    "stop_timeline",
    "grouped_allgather",
    "grouped_allgather_async",
    "grouped_reducescatter",
    "grouped_reducescatter_async",
    "poll",
    "synchronize",
    "broadcast_variables",
    "Compression",
    "ReduceOp",
    "Average",
    "Sum",
    "Adasum",
    "Min",
    "Max",
    "Product",
    "Status",
    "mpi_threads_supported",
    "mpi_built",
    "mpi_enabled",
    "gloo_built",
    "gloo_enabled",
    "nccl_built",
    "ddl_built",
    "mlsl_built",
    "xla_built",
    "xla_enabled",
    "HorovodInternalError",
    "elastic",
    "metrics",
    "metrics_snapshot",
    "serve",
    "trace",
]

from . import elastic  # noqa: E402  (hvd.elastic.run / State / ObjectState)
# hvd.metrics is the metrics subpackage, made callable so hvd.metrics()
# returns the flat snapshot dict (see metrics/__init__.py).
from . import metrics  # noqa: E402, F401
# hvd.trace is the fleet-tracing subpackage (docs/timeline.md "Fleet
# tracing"): step tap, flight recorder, KV trace shipping.
from . import trace  # noqa: E402, F401
# hvd.serve() stands up the inference-serving engine (docs/serving.md);
# the subpackage stays importable as horovod_tpu.serve.
from .serve import serve  # noqa: E402, F401
