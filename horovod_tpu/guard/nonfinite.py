"""Trace-time non-finite sentinels for the compiled (jit) paths.

The compiled-mode wiring (``horovod_tpu.jax``) applies the policy around
the fused gradient reduction:

- ``zero``  — :func:`sanitize` the local gradients BEFORE the reduce, so
  one rank's NaN never reaches the wire and the healthy ranks'
  contributions survive;
- ``warn``  — detect on the reduced gradients and log via a host
  callback (observability only);
- ``skip``  — compute a local bad-flag, reach cross-rank agreement with
  :func:`agree_flag` (a tiny psum-max — the "agreement seam"), and have
  the step apply NO update on ANY rank when any rank saw a non-finite
  gradient;
- ``abort`` — same agreed flag, surfaced to the host wrapper which
  raises ``HorovodInternalError`` (the elastic layer rolls back).

Everything here is pure jax and safe to trace; the host-side callbacks
(:func:`note_detection`) only fire when a detection actually happened.
"""

from __future__ import annotations

import logging
import threading
from typing import Any

import jax
import jax.numpy as jnp

from ..trace import SCOPE_GUARD

logger = logging.getLogger("horovod_tpu.guard")

# Per-thread trace ledger for the skip/abort agreement seam: the analysis
# lint (guard-skip-no-agreement) consumes it to catch a streamed-overlap
# step traced under policy "skip" that never emits the agreement
# collective — without the seam, ranks could disagree about skipping and
# deadlock/diverge. Mirrors ops/fusion._stream_trace.
_seam_trace = threading.local()


def _note_seam() -> None:
    d = getattr(_seam_trace, "n", 0)
    _seam_trace.n = d + 1


def take_seam_registrations() -> int:
    """Return and reset this thread's agreement-seam registration count
    since the last take (consumed once per step trace)."""
    n = getattr(_seam_trace, "n", 0)
    _seam_trace.n = 0
    return int(n)


def _float_leaves(tree: Any):
    return [
        l for l in jax.tree.leaves(tree)
        if jnp.issubdtype(jnp.result_type(l), jnp.floating)
    ]


@jax.named_scope(SCOPE_GUARD)
def local_flag(tree: Any) -> jax.Array:
    """1.0 when any float leaf of ``tree`` holds a non-finite value on
    THIS rank, else 0.0 (float32 so it can ride a psum)."""
    leaves = _float_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    bad = [jnp.any(~jnp.isfinite(l)) for l in leaves]
    flag = bad[0]
    for b in bad[1:]:
        flag = jnp.logical_or(flag, b)
    return flag.astype(jnp.float32)


@jax.named_scope(SCOPE_GUARD)
def sanitize(tree: Any) -> Any:
    """Replace non-finite entries of every float leaf with 0 (policy
    ``zero``). Non-float leaves pass through untouched."""
    def fix(l):
        if not jnp.issubdtype(jnp.result_type(l), jnp.floating):
            return l
        return jnp.where(jnp.isfinite(l), l, jnp.zeros_like(l))

    return jax.tree.map(fix, tree)


@jax.named_scope(SCOPE_GUARD)
def agree_flag(flag: jax.Array, axis_name: Any) -> jax.Array:
    """Cross-rank agreement on the skip/abort flag: psum over the
    reduction axis (or axes) — nonzero on EVERY rank when ANY rank
    flagged, so no rank applies a step another rank skipped. This is the
    agreement seam the collective lint checks for under streamed
    overlap + policy skip."""
    _note_seam()
    axes = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    out = flag
    for ax in axes:
        out = jax.lax.psum(out, ax)
    return (out > 0).astype(jnp.float32)


def note_detection(policy: str, path: str):
    """Host callback factory: increments the guard counters and appends a
    deterministic guard event when a trace-time detection fired. The
    callback body only runs when ``flag`` is nonzero at runtime."""
    from . import _count, record_guard_event

    def cb(flag):
        if not bool(flag):
            return
        _count("hvd_guard_nonfinite_total", 1.0, policy=policy, path=path)
        if policy == "skip":
            _count("hvd_guard_skipped_steps_total")
        record_guard_event(f"nonfinite-{policy}", path)
        if policy == "warn":
            logger.warning(
                "non-finite guard: non-finite gradients detected in the "
                "%s path (policy warn); the update proceeds", path,
            )
        elif policy == "skip":
            logger.warning(
                "non-finite guard: skipping this optimizer step on every "
                "rank (cross-rank agreed, %s path)", path,
            )

    def emit(flag):
        jax.debug.callback(cb, flag)

    return emit


@jax.named_scope(SCOPE_GUARD)
def select_on_flag(flag: jax.Array, when_set: Any, when_clear: Any) -> Any:
    """Leaf-wise select between two same-structure pytrees on a scalar
    flag (used to keep params/opt-state unchanged on a skipped step)."""
    keep = flag > 0

    def pick(a, b):
        return jnp.where(keep, a, b)

    return jax.tree.map(pick, when_set, when_clear)
