"""ctypes binding to the native control-plane core (libhvd_core.so).

Parity with the reference's ``horovod/common/basics.py`` (HorovodBasics
loading the C library and exposing init/rank/size/...), extended with the
plan-queue handshake: the native core negotiates/fuses/caches and emits
execution plans; Python executes them on the XLA data plane and reports
completion.
"""

from __future__ import annotations

import ctypes
import fcntl
import glob
import json
import os
import subprocess
from typing import Any, List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_CPP_DIR = os.path.join(_REPO_ROOT, "cpp")
_LIB_PATH = os.path.join(_CPP_DIR, "libhvd_core.so")

_lib: Optional[ctypes.CDLL] = None


class NativeCoreUnavailable(RuntimeError):
    pass


def _stale() -> bool:
    """True when the library is missing or older than anything it is
    built from."""
    if not os.path.exists(_LIB_PATH):
        return True
    built = os.path.getmtime(_LIB_PATH)
    deps = [os.path.join(_CPP_DIR, "Makefile")]
    for pattern in ("src/*.cc", "include/hvd/*.h"):
        deps += glob.glob(os.path.join(_CPP_DIR, pattern))
    return any(os.path.getmtime(d) > built for d in deps)


def ensure_built() -> str:
    """Bring libhvd_core.so up to date with cpp/src, so a stale binary is
    never used as it stands. Where the sources are present and one is
    newer than the library (or the library is missing), ``make`` rebuilds
    it; ranks of one host start together, so the build is serialized on a
    lock file. An installed package ships the library without its sources
    (setup.py): it is loaded as it is, and nothing is written."""
    if not os.path.exists(os.path.join(_CPP_DIR, "Makefile")):
        if os.path.exists(_LIB_PATH):
            return _LIB_PATH
        raise NativeCoreUnavailable(
            f"no native core at {_LIB_PATH} and no sources to build it from"
        )
    if not _stale():
        return _LIB_PATH
    try:
        with open(os.path.join(_CPP_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            subprocess.run(
                ["make", "-j", str(min(8, os.cpu_count() or 1)),
                 "-C", _CPP_DIR],
                check=True, capture_output=True,
            )
    except (subprocess.CalledProcessError, OSError) as e:
        out = getattr(e, "stderr", b"") or b""
        raise NativeCoreUnavailable(
            f"failed to build native core: {out.decode()[:500]}"
        ) from e
    return _LIB_PATH


def load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    path = ensure_built()
    lib = ctypes.CDLL(path)
    lib.hvd_core_init.restype = ctypes.c_int
    lib.hvd_core_init.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_longlong,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int,
    ]
    lib.hvd_core_shutdown.restype = None
    lib.hvd_core_flush_hint.restype = None
    lib.hvd_core_initialized.restype = ctypes.c_int
    for fn in ("rank", "size", "local_rank", "local_size", "cross_rank",
               "cross_size"):
        getattr(lib, f"hvd_core_{fn}").restype = ctypes.c_int
    lib.hvd_core_enqueue.restype = ctypes.c_longlong
    lib.hvd_core_enqueue.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_double, ctypes.c_double,
        ctypes.c_longlong, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int,
    ]
    lib.hvd_core_grouped_splits.restype = ctypes.c_longlong
    lib.hvd_core_grouped_splits.argtypes = []
    lib.hvd_core_register_process_set.restype = ctypes.c_int
    lib.hvd_core_register_process_set.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int,
    ]
    lib.hvd_core_remove_process_set.restype = ctypes.c_int
    lib.hvd_core_remove_process_set.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.hvd_core_enqueue_join.restype = ctypes.c_longlong
    lib.hvd_core_enqueue_join.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.hvd_core_next_plan.restype = ctypes.c_int
    lib.hvd_core_next_plan.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
    ]
    lib.hvd_core_plan_done.restype = None
    lib.hvd_core_plan_done.argtypes = [
        ctypes.c_ulonglong, ctypes.c_int, ctypes.c_char_p, ctypes.c_double,
        ctypes.c_longlong,
    ]
    lib.hvd_core_ticket_status.restype = ctypes.c_int
    lib.hvd_core_ticket_status.argtypes = [
        ctypes.c_ulonglong, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.hvd_core_cycle_time_ms.restype = ctypes.c_double
    lib.hvd_core_tuned_flags.restype = ctypes.c_int
    lib.hvd_core_cache_size.restype = ctypes.c_longlong
    lib.hvd_core_start_timeline.restype = ctypes.c_int
    lib.hvd_core_start_timeline.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.hvd_core_stop_timeline.restype = None
    lib.hvd_core_fusion_threshold.restype = ctypes.c_longlong
    lib.hvd_core_timeline_activity.restype = None
    lib.hvd_core_timeline_activity.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int,
    ]
    _lib = lib
    return lib


class NativeCore:
    """Thin OO wrapper over the C ABI."""

    ERRBUF = 4096

    def __init__(self):
        self.lib = load()

    def init(self, cfg, topo, coord_addr: str = "", coord_port: int = 0) -> None:
        err = ctypes.create_string_buffer(self.ERRBUF)
        log_levels = {"trace": 0, "debug": 1, "info": 2, "warning": 3,
                      "warn": 3, "error": 4}
        rc = self.lib.hvd_core_init(
            topo.rank, topo.size, topo.local_rank, topo.local_size,
            topo.cross_rank, topo.cross_size,
            ctypes.c_double(cfg.cycle_time_ms),
            ctypes.c_longlong(cfg.fusion_threshold_bytes),
            cfg.cache_capacity,
            0 if cfg.stall_check_disable else int(cfg.stall_warning_time_seconds),
            int(cfg.stall_shutdown_time_seconds),
            1 if cfg.autotune else 0,
            cfg.autotune_warmup_samples,
            cfg.autotune_steps_per_sample,
            log_levels.get(cfg.log_level.lower(), 2),
            cfg.timeline_filename.encode(),
            coord_addr.encode(),
            coord_port,
            cfg.autotune_log_file.encode(),
            1 if cfg.hierarchical_allreduce else 0,
            1 if cfg.hierarchical_allgather else 0,
            err, self.ERRBUF,
        )
        if rc != 0:
            raise RuntimeError(f"native core init failed: {err.value.decode()}")

    def shutdown(self) -> None:
        self.lib.hvd_core_shutdown()

    def flush_hint(self) -> None:
        """Tell the core a producer is now blocked waiting: the next
        cycle may seal immediately (skip the fusion grace/linger). No-op
        on cores built before the export existed."""
        fn = getattr(self.lib, "hvd_core_flush_hint", None)
        if fn is not None:
            fn()

    def initialized(self) -> bool:
        return bool(self.lib.hvd_core_initialized())

    def enqueue(self, request_type: int, name: str, dtype: int,
                shape, root_rank: int, reduce_op: int,
                prescale: float, postscale: float,
                group_id: int = 0, group_size: int = 0,
                process_set_id: int = 0) -> int:
        err = ctypes.create_string_buffer(self.ERRBUF)
        arr = (ctypes.c_longlong * len(shape))(*shape)
        ticket = self.lib.hvd_core_enqueue(
            request_type, name.encode(), dtype, arr, len(shape), root_rank,
            reduce_op, ctypes.c_double(prescale), ctypes.c_double(postscale),
            ctypes.c_longlong(group_id), group_size, process_set_id,
            err, self.ERRBUF,
        )
        if ticket < 0:
            raise _CoreError(-ticket, err.value.decode())
        return int(ticket)

    def register_process_set(self, psid: int, ranks) -> None:
        err = ctypes.create_string_buffer(self.ERRBUF)
        arr = (ctypes.c_int * len(ranks))(*ranks)
        rc = self.lib.hvd_core_register_process_set(
            psid, arr, len(ranks), err, self.ERRBUF
        )
        if rc != 0:
            raise _CoreError(-rc, err.value.decode())

    def remove_process_set(self, psid: int) -> None:
        err = ctypes.create_string_buffer(self.ERRBUF)
        rc = self.lib.hvd_core_remove_process_set(psid, err, self.ERRBUF)
        if rc != 0:
            raise _CoreError(-rc, err.value.decode())

    def grouped_splits(self) -> int:
        """Groups that could not fuse into a single plan (heterogeneous
        member signatures) since init."""
        return int(self.lib.hvd_core_grouped_splits())

    def enqueue_join(self) -> int:
        err = ctypes.create_string_buffer(self.ERRBUF)
        ticket = self.lib.hvd_core_enqueue_join(err, self.ERRBUF)
        if ticket < 0:
            raise _CoreError(-ticket, err.value.decode())
        return int(ticket)

    def next_plan(self, timeout_ms: int = 100, bufsize: int = 1 << 20):
        buf = ctypes.create_string_buffer(bufsize)
        r = self.lib.hvd_core_next_plan(buf, bufsize, timeout_ms)
        if r > 0:
            return json.loads(buf.value.decode())
        return r  # 0 timeout, -1 shutdown, -2 too small

    def plan_done(self, plan_id: int, status: int, error: str,
                  duration_s: float, bytes_moved: int) -> None:
        self.lib.hvd_core_plan_done(
            plan_id, status, error.encode(), ctypes.c_double(duration_s),
            ctypes.c_longlong(bytes_moved),
        )

    def ticket_status(self, ticket: int):
        """Returns (state, error): state 0=in-progress, 1=ok, <0 error."""
        err = ctypes.create_string_buffer(self.ERRBUF)
        r = self.lib.hvd_core_ticket_status(ticket, err, self.ERRBUF)
        return r, (err.value.decode() if r < 0 else "")

    def cycle_time_ms(self) -> float:
        return float(self.lib.hvd_core_cycle_time_ms())

    def fusion_threshold(self) -> int:
        return int(self.lib.hvd_core_fusion_threshold())

    def tuned_flags(self) -> int:
        """Autotuned categorical bitmask: bit0 hierarchical_allreduce,
        bit1 hierarchical_allgather, bit2 cache_enabled."""
        return int(self.lib.hvd_core_tuned_flags())

    def cache_size(self) -> int:
        return int(self.lib.hvd_core_cache_size())

    def start_timeline(self, path: str, mark_cycles: bool = False) -> int:
        """Start the catapult timeline at runtime (later-reference
        hvd.start_timeline). Returns 0 ok, nonzero StatusCode."""
        return int(self.lib.hvd_core_start_timeline(
            path.encode(), 1 if mark_cycles else 0
        ))

    def stop_timeline(self) -> None:
        self.lib.hvd_core_stop_timeline()

    def timeline_activity(self, tensor: str, activity: str, begin: bool):
        self.lib.hvd_core_timeline_activity(
            tensor.encode(), activity.encode(), 1 if begin else 0
        )


class _CoreError(RuntimeError):
    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code
