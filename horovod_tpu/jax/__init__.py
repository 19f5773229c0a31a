"""horovod_tpu.jax — the compiled-mode (performance-path) binding.

Where the reference's framework bindings enqueue per-tensor async ops into a
background loop (``horovod/tensorflow/__init__.py``,
``horovod/torch/__init__.py``), the TPU-native compiled mode moves the whole
reduction *inside* the jitted training step: gradients are bucket-fused at
trace time and reduced with single large XLA collectives over a named mesh
axis. This keeps Horovod's semantics (``DistributedOptimizer`` wrapping an
inner optimizer, Average/Sum/Adasum ops, fp16/bf16 compression) while letting
XLA overlap the collectives with backprop on ICI.

Typical use::

    import horovod_tpu.jax as hvd

    mesh = hvd.build_mesh()                 # one "data" axis over all chips
    tx = hvd.DistributedOptimizer(optax.sgd(0.01))
    step = hvd.make_train_step(loss_fn, tx, mesh)
    params = hvd.broadcast_variables(params, mesh)     # rank-0 state
    params, opt_state, loss = step(params, opt_state, batch)
"""

from __future__ import annotations

import time as _time

_IMPORT_START = _time.perf_counter()  # before `import jax`: trace.note_import

import contextlib as _contextlib
import functools as _functools
import logging
import math as _math
from typing import Any, Callable, Optional
from typing import NamedTuple as _NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import trace as _trace
from ..common.compression import Compression
from ..common.types import Adasum, Average, ReduceOp, Sum
from ..guard import nonfinite as _nf
from ..guard import resolve_policy as _resolve_nonfinite
from ..ops import collectives as _c
from ..ops import fusion as _fusion
from ..ops import quantized as _q
from ..ops.adasum import adasum_reduce_fn
from ..ops.quantized import EFState, ef_like
from ..parallel.zero import (
    Zero1State,
    init_zero1_stream_state,
    zero1_posthoc_reduce,
    zero1_stream_update,
)
from ..parallel.reshard import (  # noqa: F401 (re-exported API)
    LayoutManifest,
    Zero1Layout,
    build_manifest,
    reshard_zero1_state,
    zero1_layout_from_params,
)
from ..parallel.mesh import (
    CROSS_AXIS,
    DATA_AXIS,
    LOCAL_AXIS,
    POD_AXIS,
    build_hierarchical_mesh,
    build_mesh,
    build_three_level_mesh,
    hierarchy_axes,
)

_logger = logging.getLogger("horovod_tpu")

# Before any program of this process is traced: the build ledger hears JAX's
# trace, lower and compile-or-load events from here on (docs/timeline.md).
_trace.install_build_listeners()

# Compiled-mode users reach collectives through jit, never through hvd.init;
# an explicit HOROVOD_XLA_PERF_PRESET must land in LIBTPU_INIT_ARGS before the
# first backend touch, so the resolver runs at import (idempotent; applies
# nothing unless the variable is set).
from ..common import env as _env_mod  # noqa: E402

_env_mod.apply_xla_perf_preset()

def _shard_map(fn, mesh, *, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with varying-axes checking off by default.

    ``check=True`` enables replication/varying-ness tracking — REQUIRED
    when differentiating through an in-body ``psum`` (e.g. the tensor-
    parallel row-parallel matmul) unless the conjugates are hand-written
    (as ``parallel/tp.py`` does where it finds itself unchecked): without
    it the psum transpose cannot see that the cotangent is replicated and
    multiplies gradients by the axis size. The default stays off for the
    collective-executor bodies, whose hand-written patterns (bucket
    packing, ppermute rings) the checker cannot type as replicated.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


# In-jit primitives (usable inside shard_map/pmap bodies).
allreduce = _c.allreduce
allgather = _c.allgather
broadcast = _c.broadcast
alltoall = _c.alltoall
reducescatter = _c.reducescatter
hierarchical_allreduce = _c.hierarchical_allreduce
hierarchical_allgather = _c.hierarchical_allgather
hierarchical_reducescatter = _c.hierarchical_reducescatter
hierarchical_broadcast = _c.hierarchical_broadcast
hierarchical_alltoall = _c.hierarchical_alltoall

# Streamed (overlap) gradient reduction: register a parameter subtree (or a
# scanned layer stack's body) so its gradients are bucket-allreduced INSIDE
# the backward pass — see ops/fusion.py and docs/overlap.md.
reduce_in_backward = _fusion.reduce_in_backward
stream_scan_body = _fusion.stream_scan_body
stream_param_groups = _fusion.stream_param_groups

# Composed-parallelism sharding-rules engine (parallel/rules.py;
# docs/parallelism.md "Composed DP x TP fast path"): regex ->
# PartitionSpec tables drive mesh placement + gather/shard fns,
# preflighted by the Pass 5 validator. GPT_RULES is the shipped DP x TP
# table for models/transformer.py.
from ..parallel.rules import (  # noqa: E402
    GPT_RULES,
    gather_tree,
    local_shard_tree,
    make_shard_and_gather_fns,
    match_partition_rules,
    preflight_rules,
    shard_tree,
)


def collective_plan(collective: str = "allreduce",
                    nbytes: int = 4 * 1024 * 1024,
                    op: Optional[ReduceOp] = None,
                    wire_dtype: str = "f32") -> dict:
    """Compiled-mode alias of :func:`horovod_tpu.collective_plan` —
    the topology compositor's selected plan for one collective at one
    payload size (docs/topology.md). ``wire_dtype="int8"`` prices the
    int8+scales wire format (allreduce SUM/AVERAGE only): compressed
    bytes on the slow hop(s), full precision over ICI."""
    from .. import collective_plan as _cp

    return _cp(collective, nbytes, op, wire_dtype=wire_dtype)


def _resolve_quantized(quantized: Optional[bool]) -> bool:
    """Resolve the int8-wire knob: explicit argument >
    ``HOROVOD_QUANTIZED_WIRE`` env (1/true/int8 = on) > off."""
    if quantized is not None:
        return bool(quantized)
    import os

    from ..common import env as _env

    raw = os.environ.get(_env.HOROVOD_QUANTIZED_WIRE, "").strip().lower()
    return raw in ("1", "true", "yes", "on", "int8")


def error_feedback_state(opt_state: Any, params: Any) -> EFState:
    """Wrap an inner optimizer state with a zero error-feedback residual
    — the opt_state shape ``make_train_step(quantized=True)`` threads.
    Passing a plain opt_state into such a step also works (the residual
    is materialized as zeros on the first call and the step returns an
    :class:`EFState` from then on); this helper makes the structure
    explicit up front, e.g. for ``lax.scan`` carries that need a stable
    shape."""
    return EFState(inner=opt_state, residual=ef_like(params))


def _resolve_hierarchical(hierarchical, mesh: Optional[Mesh] = None):
    """Resolve the tri-state ``hierarchical`` knob (docs/topology.md):

    - ``False`` / ``True`` pass through (True = the forced two-level
      lowering, reference parity).
    - ``"auto"`` consults the topology compositor: with a mesh, the
      hierarchy axes the caller built decide (a deliberate (pod,) cross,
      local grid -> per-bucket plan selection; a flat data mesh -> flat);
      without one, the detected process topology's homogeneity-gated
      model decides.

    Returns ``(mode, axes)`` where mode is False / True / "planned" and
    axes is the hierarchy axis tuple for planned mode (None otherwise).
    """
    if hierarchical == "auto":
        if mesh is not None:
            axes = hierarchy_axes(mesh)
            if axes:
                return "planned", axes
            return False, None
        from ..topo import resolve_model

        if resolve_model().eligible:
            return "planned", (CROSS_AXIS, LOCAL_AXIS)
        return False, None
    if hierarchical == "planned":
        return "planned", None
    return bool(hierarchical), None


def _select_reduce_fn(op: ReduceOp, hierarchical, quantized: bool = False,
                      topo_algorithm: Optional[str] = None):
    if op == ReduceOp.ADASUM:
        return adasum_reduce_fn
    if hierarchical == "planned":
        from ..topo import compositor as _compositor

        return _compositor.auto_reduce_fn(
            quantized=quantized, algorithm=topo_algorithm
        )
    if quantized:
        # Flat: every hop int8 (the EQuARX ring). Hierarchical: int8 on
        # the outermost (DCN) hop only — reduce-scatter/all-gather stay
        # full precision over ICI (docs/topology.md).
        return _q.quantized_reduce_fn(
            "two-level" if hierarchical else "flat"
        )
    if hierarchical:
        # axis_name must be the (cross, local) tuple: reduce-scatter rides
        # ICI (local), the shard psum rides DCN (cross). The streamed
        # path's two-level reduce, post-hoc.
        return _fusion._hier_reduce_fn
    return _c.allreduce


def _normalize_axis(axis_name, hierarchical):
    """hierarchical=True (or "planned") defaults the axis to the
    (cross, local) pair of a hierarchical mesh; a plain psum uses the
    tuple directly (XLA reduces over both axes), while the hierarchical
    reduce path splits it."""
    if hierarchical and isinstance(axis_name, str):
        if axis_name != DATA_AXIS:
            raise ValueError(
                "hierarchical=True needs a (cross, local) axis tuple, got "
                f"{axis_name!r}"
            )
        return (CROSS_AXIS, LOCAL_AXIS)
    return axis_name


def allreduce_gradients(
    grads: Any,
    *,
    op: ReduceOp = Average,
    axis_name=DATA_AXIS,
    fusion_threshold_bytes: Optional[int] = None,
    compression=Compression.none,
    hierarchical: Any = False,
    quantized: Optional[bool] = None,
    nonfinite: Optional[str] = None,
    topo_algorithm: Optional[str] = None,
) -> Any:
    """Fusion-bucketed allreduce of a gradient pytree (in-jit).

    The compiled-mode equivalent of the reference's per-gradient
    ``hvd.allreduce`` + background fusion: same-dtype leaves are concatenated
    into buckets up to the fusion threshold and each bucket becomes one XLA
    collective (see ops/fusion.py). ``quantized=True`` (None reads
    ``HOROVOD_QUANTIZED_WIRE``) moves each float bucket through the
    int8-wire ring allreduce (``ops/quantized.py``, ~1% gradient noise at
    8 ranks) instead of a full-precision ``psum``; composed with
    ``hierarchical`` the wire compresses ONLY the outermost (DCN) hop —
    reduce-scatter/all-gather stay full precision over ICI. SUM/AVERAGE
    only; integer buckets always reduce exactly.
    ``fusion_threshold_bytes=None`` resolves HOROVOD_FUSION_THRESHOLD
    (64 MB default, reference parity).

    ``nonfinite`` (None reads ``HOROVOD_GUARD_NONFINITE``) applies the
    non-finite sentinel around the reduce: ``zero`` sanitizes the local
    gradients BEFORE the wire (a poisoned rank's NaN never reaches its
    peers), ``warn`` detects on the reduced result and logs. The
    step-level policies (``skip``/``abort``) are applied by
    ``DistributedOptimizer`` / ``make_train_step``, not here.

    ``topo_algorithm`` pins one compositor lowering for every bucket
    (the offline tuner's verdict, docs/autotune.md) — meaningful only
    when ``hierarchical`` resolves to planned mode.
    """
    fusion_threshold_bytes = _fusion.default_threshold_bytes(
        fusion_threshold_bytes
    )
    quantized = _resolve_quantized(quantized)
    if hierarchical == "auto":
        hierarchical, _ = _resolve_hierarchical(hierarchical)
    axis_name = _normalize_axis(axis_name, hierarchical)
    nonfinite_policy = _resolve_nonfinite(nonfinite)
    if nonfinite_policy == "zero":
        grads = _nf.sanitize(grads)
    from ..analysis import preflight as _preflight

    if _preflight.enabled():
        # Opt-in trace-time pre-flight (HOROVOD_TPU_STATIC_CHECKS=1):
        # validates the fusion bucket plan and that the reduction axis is
        # actually bound before the collective is traced in.
        _preflight.check_gradient_tree(
            grads, fusion_threshold_bytes, axis_name
        )
    if quantized:
        if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            raise ValueError(
                "quantized=True supports SUM/AVERAGE reduction only"
            )
        if compression is not Compression.none:
            raise ValueError(
                "quantized=True already compresses the wire to int8; "
                "stacking cast compression would add loss for no "
                "bandwidth win"
            )
    reduce_fn = _select_reduce_fn(op, hierarchical, quantized,
                                  topo_algorithm=topo_algorithm)
    if compression is not Compression.none:
        leaves, treedef = jax.tree.flatten(grads)
        compressed = [compression.compress(l) for l in leaves]
        grads = jax.tree.unflatten(treedef, [c for c, _ in compressed])
        ctxs = [ctx for _, ctx in compressed]
    reduced = _fusion.fused_allreduce(
        grads,
        op=op,
        axis_name=axis_name,
        threshold_bytes=fusion_threshold_bytes,
        reduce_fn=reduce_fn,
        wire_dtype=(
            "int8" if quantized and not hierarchical else "f32"
        ),
    )
    if compression is not Compression.none:
        leaves, treedef = jax.tree.flatten(reduced)
        leaves = [compression.decompress(l, ctx) for l, ctx in zip(leaves, ctxs)]
        reduced = jax.tree.unflatten(treedef, leaves)
    if nonfinite_policy == "warn":
        # Post-reduce detection: a NaN from ANY rank propagates through
        # SUM/AVERAGE, so every rank observes (and logs) the same event.
        _nf.note_detection("warn", "reduce")(_nf.local_flag(reduced))
    return reduced


def _check_overlap_rejections(overlap: bool, quantized: bool, op: ReduceOp):
    if quantized and op not in _fusion._QUANTIZABLE_OPS:
        raise ValueError(
            f"quantized=True supports {_fusion._QUANTIZABLE_OPS}; got {op} "
            "(per-hop int8 requantization accumulates in f32, which is "
            "only sound for additive reductions)"
        )
    if not overlap:
        return
    if op not in _fusion._STREAMABLE_OPS:
        raise ValueError(
            f"overlap=True supports elementwise reduce ops "
            f"{_fusion._STREAMABLE_OPS}; got {op}"
        )


def _resolve_error_feedback(error_feedback: Optional[bool],
                            quantized: bool, hierarchical) -> bool:
    """EF defaults ON for the flat int8 wire (where every byte is
    compressed and the residual compensates this rank's quantizer) and
    OFF for hierarchical/planned DCN-only compression (the quantizer
    sees post-local-reduction shards no per-rank residual can
    attribute); forcing it on there is an error, not a silent noop."""
    if not quantized:
        if error_feedback:
            raise ValueError("error_feedback=True requires quantized=True")
        return False
    if hierarchical:
        if error_feedback:
            raise ValueError(
                "error feedback compensates the flat int8 ring; the "
                "hierarchical DCN-only wire has no per-rank quantizer "
                "to compensate — leave error_feedback unset"
            )
        return False
    return True if error_feedback is None else bool(error_feedback)


def _open_state(opt_state, like, *, use_ef: bool, zero1_lead: int = 0,
                expects: str = ""):
    """Split what a step threads as ``opt_state`` into ``(inner, ef,
    close)``: the state the update consumes, the error-feedback residual
    (None without EF) and ``close(new_inner, new_ef)``, which puts both
    back in the shape they came in. The residual rides the opt_state as
    ``EFState(inner, residual)``; a plain opt_state (first step, old
    checkpoint) materializes a zero residual shaped like ``like``. With
    ``zero1_lead`` the state is a :class:`Zero1State` stacked on that
    many leading axes (its row is ``s[0]``, or ``s[0, 0]`` on a (data,
    model) mesh) and carries the SHARDED residual in ``.ef``."""
    if zero1_lead:
        if not isinstance(opt_state, Zero1State):
            raise TypeError(f"{expects}; got {type(opt_state).__name__}")
        row = jax.tree.map(lambda s: s[(0,) * zero1_lead], opt_state)
        if use_ef and row.ef is None:
            raise ValueError(
                "the quantized zero1 wire carries a SHARDED "
                "error-feedback residual in the optimizer state; "
                "rebuild it with init_zero1_stream_state(..., "
                "quantized=True) or pass error_feedback=False"
            )

        def close(new_opt, new_ef):
            return jax.tree.map(
                lambda s: s[(None,) * zero1_lead],
                Zero1State(opt=new_opt, ef=new_ef if use_ef else row.ef),
            )

        return row.opt, (row.ef if use_ef else None), close
    if not use_ef:
        return opt_state, None, lambda new_inner, _: new_inner
    if isinstance(opt_state, EFState):
        opt_state, ef = opt_state.inner, opt_state.residual
    else:
        ef = ef_like(like)
    return opt_state, ef, lambda new_inner, new_ef: EFState(
        inner=new_inner, residual=new_ef
    )


def _exchange_posthoc(
    grads: Any, ef: Any, *, nonfinite: str, labels, op: ReduceOp,
    axis_name: Any, threshold_bytes: Optional[int], quantized: bool,
    reduce: bool = True, flag_axes: Any = None, pre_flag: bool = True,
    recheck_streamed: bool = False,
    first_bucket_bytes: Optional[int] = None, zero1: bool = False,
    fused_label: Optional[str] = None, compression=Compression.none,
    hierarchical: Any = False, algorithm: Optional[str] = None,
):
    """The ONE place where a tree of local gradients becomes reduced
    gradients under the non-finite guard: the step ``make_train_step``
    builds and both ``DistributedOptimizer`` wrappers come through here.
    Returns ``(reduced, new_ef, flag)``; ``flag`` is None unless the
    resolved policy ``nonfinite`` is ``skip``/``abort``.

    The wire follows what the caller states, under the keywords
    ``_fusion.stream_param_groups`` takes for the streamed form of the
    same exchange (one dict serves both). ``reduce=False``: the
    gradients arrived ALREADY reduced from a streamed backward (the
    custom_vjp backward rules issued the bucket collectives); only the
    guard's post-reduce half applies, and ``ef`` passes through.

    ``labels``: the metric labels of (the post-hoc ``warn`` note, the
    streamed one, the skip/abort note). ``flag_axes`` (default
    ``axis_name``): the axes the flag is agreed over. ``pre_flag=False``
    leaves out the pre-reduce detection (zero1 is SUM/AVERAGE-only, so a
    NaN from any rank propagates into its shard image).
    ``recheck_streamed``: the registrations were not the caller's own,
    so streamed gradients are guarded as if local."""
    guarded = nonfinite in ("skip", "abort")
    local = reduce or recheck_streamed
    flag = None
    if guarded and pre_flag and local:
        # Pre-reduce local detection: catches a bad local gradient even
        # under MIN/MAX reductions, where NaN may not propagate.
        flag = _nf.local_flag(grads)
    if nonfinite == "zero" and local:
        # Sentinel BEFORE the wire (a poisoned rank's NaN never reaches
        # its peers, and would poison its block's scale in the
        # quantizer). Streamed groups sanitize pre-reduce when registered
        # with the policy; sanitizing the already-reduced grads again is
        # a harmless belt for manual registrations.
        grads = _nf.sanitize(grads)
    new_ef = ef
    if not reduce:
        pass
    elif zero1:
        # Per-bucket reduce-scatter into shard images; ef is SHARDED.
        grads, new_ef = zero1_posthoc_reduce(
            grads, op=op, axis_name=axis_name, quantized=quantized, ef=ef,
            threshold_bytes=threshold_bytes,
            first_bucket_bytes=first_bucket_bytes,
        )
    elif ef is not None:
        # Reduce g + e over the int8 wire and carry the fresh residual.
        grads, new_ef = _fusion.quantized_ef_allreduce(
            grads, ef, op=op, axis_name=axis_name,
            threshold_bytes=threshold_bytes, label="posthoc-ef",
        )
    elif fused_label is not None:
        # The composed path's data-axis buckets: never re-planned.
        grads = _fusion.fused_allreduce(
            grads, op=op, axis_name=axis_name,
            threshold_bytes=threshold_bytes,
            reduce_fn=_q.quantized_reduce_fn("flat") if quantized else None,
            label=fused_label,
            wire_dtype="int8" if quantized else "f32",
        )
    else:
        grads = allreduce_gradients(
            grads, op=op, axis_name=axis_name,
            fusion_threshold_bytes=threshold_bytes,
            compression=compression, hierarchical=hierarchical,
            quantized=quantized, nonfinite="off", topo_algorithm=algorithm,
        )
    if nonfinite == "warn":
        # Post-reduce detection: a NaN from ANY rank propagates through
        # SUM/AVERAGE, so every rank observes (and logs) the same event.
        _nf.note_detection("warn", labels[0 if reduce else 1])(
            _nf.local_flag(grads)
        )
    if guarded:
        # Agreement seam (psum of the flag, the shape the preemption
        # commit check uses): no rank applies a step another rank
        # skipped. Post-reduce detection is OR-ed in so an overflow
        # created BY the summation is also caught; for streamed
        # gradients it is the only detection point (the flag cannot be
        # carried out of the custom_vjp backward rules).
        post = _nf.local_flag(grads)
        flag = post if flag is None else jnp.maximum(flag, post)
        flag = _nf.agree_flag(
            flag, axis_name if flag_axes is None else flag_axes
        )
        _nf.note_detection(nonfinite, labels[2])(flag)
    return grads, new_ef, flag


@_functools.cache
def _distributed_transformation():
    """The type both ``DistributedOptimizer`` forms return (built on first
    use: optax is an optional dependency): an
    ``optax.GradientTransformation`` that ``make_train_step`` can
    recognise and open. Beside ``init``/``update`` it carries ``wrapped``
    (the optimizer it was built round), ``options`` (every keyword the
    wrapper was built with) and ``stated`` (the names of those the caller
    moved off their defaults)."""
    import optax

    class DistributedTransformation(optax.GradientTransformation):
        pass

    return DistributedTransformation


def _distributed(init_fn, update_fn, wrapped, **options):
    """A wrapper's ``(init, update)`` as the openable type; ``options``
    are ``DistributedOptimizer`` keywords, as the wrapper was built."""
    import inspect

    tx = _distributed_transformation()(init_fn, update_fn)
    defaults = inspect.signature(DistributedOptimizer).parameters
    tx.wrapped, tx.options = wrapped, options
    tx.stated = frozenset(
        name for name, value in options.items()
        if value != defaults[name].default
    )
    return tx


def _zero1_distributed_optimizer(
    optimizer,
    *,
    op: ReduceOp,
    axis_name: str,
    fusion_threshold_bytes: Optional[int],
    compression,
    hierarchical: Any,
    quantized: bool,
    error_feedback: Optional[bool],
    overlap: bool,
    nonfinite: Optional[str],
    zero1_shards: Optional[int],
    tuned: Any,
):
    """The ``DistributedOptimizer(zero1=True)`` construction — see the
    public wrapper's docstring for the contract."""
    if zero1_shards is None or int(zero1_shards) < 1:
        raise ValueError(
            "DistributedOptimizer(zero1=True) needs zero1_shards=<data-"
            "axis size>: init builds the sharded state before any axis "
            "is bound, so the shard count cannot be inferred"
        )
    n_shards = int(zero1_shards)
    _check_zero1_args(op, compression, None)
    if bool(hierarchical):
        raise ValueError(
            "DistributedOptimizer(zero1=True) runs over the flat data "
            "axis; hierarchical zero1 lives in make_train_step(zero1="
            "True, hierarchical='auto'), which owns the mesh"
        )
    if error_feedback:
        raise ValueError(
            "zero1 error feedback rides the streamed backward's side "
            "channel, which only make_train_step(zero1=True, "
            "quantized=True) can thread — leave error_feedback unset"
        )
    if tuned not in (None, False):
        _logger.warning(
            "DistributedOptimizer(zero1=True) ignores tuned=: the "
            "sharded state layout is keyed by the knobs the state was "
            "built with — apply tunings via make_train_step(zero1=True, "
            "tuned=...)"
        )
    nonfinite_policy = _resolve_nonfinite(nonfinite)
    if nonfinite_policy in ("skip", "abort"):
        raise ValueError(
            "nonfinite skip/abort need the step-level agreement seam "
            "(make_train_step); the zero1 optax wrapper supports "
            "off/zero/warn"
        )
    knobs = dict(threshold_bytes=fusion_threshold_bytes, quantized=quantized)
    _trace.note_plan(
        optimizer="DistributedOptimizer",
        wire_dtype="int8" if quantized else "f32",
        overlap=bool(overlap), zero1=True,
    )

    def init_fn(params):
        return init_zero1_stream_state(
            optimizer, params, n_shards, error_feedback=False, **knobs
        )

    def update_fn(grads, state, params=None, **extra):
        if params is None:
            raise ValueError(
                "DistributedOptimizer(zero1=True) needs the params "
                "argument: the shard-local update slices this rank's "
                "parameter shard"
            )
        opt_rows, _, close = _open_state(
            state, None, use_ef=False, zero1_lead=1,
            expects="zero1 update expects the Zero1State this wrapper's "
                    "init built",
        )
        do_reduce = True
        if overlap:
            reg = _fusion.take_stream_registrations()
            do_reduce = reg["calls"] == 0
            if do_reduce:
                _logger.warning(
                    "overlap=True but no parameter subtree was "
                    "registered with stream_param_groups(zero1=True); "
                    "reduce-scattering post-hoc (correct, zero overlap)"
                )
        # The thinner guard: sanitize, reduce, warn; no flag (skip/abort
        # are rejected above).
        grads, _, _ = _exchange_posthoc(
            grads, None, reduce=do_reduce, nonfinite=nonfinite_policy,
            labels=("zero1-optimizer", "zero1-optimizer", None),
            op=op, axis_name=axis_name, zero1=True, **knobs,
        )
        new_params, new_opt = zero1_stream_update(
            optimizer, params, opt_rows, grads,
            axis_name=axis_name, n_shards=n_shards, **knobs,
        )
        updates = jax.tree.map(lambda a, b: a - b, new_params, params)
        return updates, close(new_opt, None)

    # What a step that opens this wrapper must know: the state init builds
    # has the untuned bucket layout and no residual.
    return _distributed(
        init_fn, update_fn, optimizer, op=op, axis_name=axis_name,
        fusion_threshold_bytes=fusion_threshold_bytes,
        compression=compression, hierarchical=hierarchical,
        quantized=quantized, error_feedback=False if quantized else None,
        overlap=overlap, nonfinite=nonfinite, tuned=False, zero1=True,
        zero1_shards=n_shards,
    )


def DistributedOptimizer(  # noqa: N802 - API parity with hvd.DistributedOptimizer
    optimizer,
    *,
    op: ReduceOp = Average,
    axis_name: str = DATA_AXIS,
    fusion_threshold_bytes: Optional[int] = None,
    compression=Compression.none,
    hierarchical: Any = False,
    quantized: Optional[bool] = None,
    error_feedback: Optional[bool] = None,
    backward_passes_per_step: int = 1,
    overlap: bool = False,
    nonfinite: Optional[str] = None,
    tuned: Any = None,
    topo_algorithm: Optional[str] = None,
    zero1: bool = False,
    zero1_shards: Optional[int] = None,
):
    """Wrap an optax ``GradientTransformation`` so its update first
    allreduces gradients across the data axis.

    API parity with ``hvd.DistributedOptimizer``
    (``horovod/tensorflow/__init__.py:409-470``): the wrapped optimizer is
    used unchanged; only the gradients it sees are averaged across ranks.
    ``backward_passes_per_step > 1`` expects the caller to accumulate
    locally (see ``GradientAccumulator``) — the divisor is folded in here, as
    the reference does in the framework layer
    (``horovod/torch/mpi_ops.py:101-124``).

    Used alone (its ``update`` called inside your own ``shard_map`` /
    ``jit``) the wrapper reduces. Handed to :func:`make_train_step` it is
    recognised and OPENED instead: the step already reduces the
    gradients, so it updates through the wrapped ``optimizer`` (after the
    ``1 / backward_passes_per_step`` scaling) and this wrapper's own
    reduction is never traced: one exchange, not two. That exchange takes
    every option stated here that ``make_train_step`` was left to default
    on; an option stated on both with different values raises a
    ``ValueError`` naming it. ``init`` is unchanged, so
    ``wrapper.init(params)`` is the state that step threads. A wrapper
    buried inside ``optax.chain(...)`` cannot be seen and still reduces
    a second time there.

    ``overlap=True`` expects the model's layers to have been registered for
    streamed reduction (``hvd.reduce_in_backward`` /
    ``hvd.stream_param_groups`` applied to the params the loss consumes):
    the gradients then arrive ALREADY reduced from inside the backward pass
    and the post-hoc reduction here is skipped. If no layer was registered
    this falls back to the post-hoc reduction with a loud warning (and an
    ``overlap-no-streaming`` finding under HOROVOD_TPU_STATIC_CHECKS=1) —
    see docs/overlap.md.

    ``nonfinite`` (None reads ``HOROVOD_GUARD_NONFINITE``, resolved when
    the wrapper is built) applies the non-finite gradient guard: ``zero``
    sanitizes before the wire, ``warn`` logs, ``skip`` reaches cross-rank
    agreement on a skip flag and applies NO update on ANY rank for that
    step, ``abort`` behaves like ``skip`` here (an optax transformation
    cannot raise usefully from inside a trace) and is surfaced as a
    raised ``HorovodInternalError`` by ``make_train_step`` — see
    docs/fault_tolerance.md "Data-plane integrity".

    ``quantized=True`` (None reads ``HOROVOD_QUANTIZED_WIRE``) moves the
    gradient buckets over the int8 wire; on the flat (non-hierarchical)
    path it carries an error-feedback residual in the optimizer state by
    default (``error_feedback``, EF-SGD: the quantization error is added
    back into the next step's gradient before quantization, preserving
    convergence). The wrapped state is then
    ``EFState(inner=<inner opt state>, residual=<f32 grads-like>)`` —
    ``tx.init(params)`` builds it, checkpoints carry it, and the guard's
    digest agreement excludes the rank-local residual. Under
    ``overlap=True`` the streamed registration owns the residual
    (``make_train_step`` threads it); this wrapper then leaves EF to the
    streamed path.

    ``tuned`` (None reads ``HOROVOD_TUNED_FILE``; a path or a
    :class:`horovod_tpu.tune.TunedConfig`) applies a pinned offline
    tuning (docs/autotune.md "Compiled-path offline tuning") to the
    knobs the caller left at their defaults. The gradient tree an
    optimizer sees carries no mesh, so only the params half of the
    tuning's step signature is checked here (``make_train_step`` checks
    both); a mismatch warns loudly and keeps the untuned defaults.
    ``topo_algorithm`` pins one compositor lowering under planned
    hierarchy — normally set via ``tuned``, exposed for hand
    experiments.

    ``zero1=True`` (with ``zero1_shards=<data-axis size>``) shards the
    optimizer state per streamed bucket (docs/overlap.md "Streamed
    ZeRO-1"): ``init`` builds the stacked :class:`Zero1State` — thread
    it through your ``shard_map`` with ``P(axis_name)`` on the leading
    axis — and ``update`` runs the shard-local optax update against the
    bucketized shard layout, returning full-tree updates
    (``gathered_new_params - params``; note ``apply_updates`` re-adds,
    so the result matches ``make_train_step(zero1=True)`` to float-add
    round-off, not bitwise). Under ``overlap=True`` the gradients must
    arrive as shard images from ``stream_param_groups(zero1=True)``;
    without registrations the wrapper reduce-scatters post-hoc (correct,
    zero overlap). Error feedback needs the backward side channel only
    ``make_train_step`` owns and is rejected here.
    """
    from .. import tune as _tune

    if zero1:
        return _zero1_distributed_optimizer(
            optimizer, op=op, axis_name=axis_name,
            fusion_threshold_bytes=fusion_threshold_bytes,
            compression=compression, hierarchical=hierarchical,
            quantized=_resolve_quantized(quantized),
            error_feedback=error_feedback, overlap=overlap,
            nonfinite=nonfinite, zero1_shards=zero1_shards,
            tuned=tuned,
        )
    tuned_cfg, tuned_source = _tune.resolve_tuned(tuned)
    caller_quantized = quantized
    caller_hierarchical = hierarchical
    caller_threshold = fusion_threshold_bytes
    quantized = _resolve_quantized(quantized)
    _check_overlap_rejections(overlap, quantized, op)
    nonfinite_policy = _resolve_nonfinite(nonfinite)
    # "auto" without a mesh in hand: the detected process topology's
    # homogeneity-gated model decides (docs/topology.md); the mesh the
    # caller traces under must then carry the (cross, local) axes.
    hierarchical, _ = _resolve_hierarchical(hierarchical)
    norm_axis = _normalize_axis(axis_name, hierarchical)
    # Under overlap the residual lives with the streamed registration
    # (the backward rule computes it); the optimizer cannot see it.
    use_ef = _resolve_error_feedback(
        error_feedback, quantized, hierarchical
    ) and not overlap
    if quantized and compression is not Compression.none:
        raise ValueError(
            "quantized=True already compresses the wire to int8; "
            "stacking cast compression would add loss for no bandwidth win"
        )

    base_knobs = {
        "fusion_threshold_bytes": fusion_threshold_bytes,
        "quantized": quantized,
        "hierarchical": hierarchical,
        "norm_axis": norm_axis,
        "use_ef": use_ef,
        "topo_algorithm": topo_algorithm,
    }
    _tuned_resolution: dict = {}

    def _knobs(tree, where):
        """Trace-time knob resolution: with a tuned config in hand, the
        first traced pytree (params at init, gradients at update — the
        same structure) decides whether the pinned knobs apply. The
        verdict is cached: init and update must agree or the EF state
        shape would be inconsistent."""
        if tuned_cfg is None:
            return base_knobs
        r = _tuned_resolution.get("r")
        if r is not None:
            return r
        live = _tune.step_signature(tree)
        matched = _tune.signatures_match(
            tuned_cfg.signature, live, require_mesh=False
        )
        if matched:
            tk = _tune.tuned_step_kwargs(tuned_cfg)
            q = (quantized if caller_quantized is not None
                 else tk["quantized"])
            h = (caller_hierarchical if caller_hierarchical is not False
                 else tk["hierarchical"])
            h, _ = _resolve_hierarchical(h)
            r = {
                "fusion_threshold_bytes": (
                    caller_threshold if caller_threshold is not None
                    else tk["fusion_threshold_bytes"]
                ),
                "quantized": q,
                "hierarchical": h,
                "norm_axis": _normalize_axis(axis_name, h),
                "use_ef": _resolve_error_feedback(
                    error_feedback, q, h
                ) and not overlap,
                "topo_algorithm": (
                    topo_algorithm if topo_algorithm is not None
                    else tk["topo_algorithm"]
                ),
            }
        else:
            _tune.warn_signature_mismatch(
                tuned_cfg, live.get("hash", "?"), "DistributedOptimizer"
            )
            r = base_knobs
        _tune.note_applied(
            tuned_source, tuned_cfg.signature_hash, matched,
            "DistributedOptimizer",
        )
        _tuned_resolution["r"] = r
        return r
    # Step-span correlation ids for loops driven by this optimizer:
    # the host-side step boundaries themselves come from wrap_step
    # or the elastic commit seam (an optax transformation runs
    # inside the caller's jit and has no host boundary of its own),
    # but every step span they record carries this wire/overlap
    # configuration.
    _trace.note_plan(
        optimizer="DistributedOptimizer",
        wire_dtype="int8" if quantized else "f32",
        overlap=bool(overlap),
    )

    def init_fn(params):
        if _knobs(params, "init")["use_ef"]:
            return error_feedback_state(optimizer.init(params), params)
        return optimizer.init(params)

    def update_fn(grads, state, params=None, **extra):
        k = _knobs(grads, "update")
        prescale = 1.0 / backward_passes_per_step if backward_passes_per_step > 1 else 1.0
        state, ef, close = _open_state(state, grads, use_ef=k["use_ef"])
        do_reduce = True
        if overlap:
            reg = _fusion.take_stream_registrations()
            from ..analysis import preflight as _preflight

            findings = _preflight.check_overlap_streaming(
                reg, len(jax.tree.leaves(grads))
            )
            # No registered layer at all → the backward reduced nothing;
            # reduce post-hoc (correct, just without overlap). Partial
            # registration keeps the streamed contract (re-reducing here
            # would double-reduce the registered layers) — the finding
            # above already warned.
            do_reduce = reg["calls"] == 0
            if _preflight.enabled():
                _preflight._raise_or_log(findings)
            else:
                for f in findings:
                    _logger.warning("%s", f.render())
        reduced, new_ef, flag = _exchange_posthoc(
            grads, ef, reduce=do_reduce, recheck_streamed=True,
            nonfinite=nonfinite_policy,
            labels=("reduce", "overlap", "optimizer"),
            op=op, axis_name=k["norm_axis"],
            threshold_bytes=k["fusion_threshold_bytes"],
            quantized=k["quantized"], compression=compression,
            hierarchical=k["hierarchical"], algorithm=k["topo_algorithm"],
        )
        if prescale != 1.0:
            reduced = jax.tree.map(lambda g: g * prescale, reduced)
        updates, new_state = optimizer.update(reduced, state, params, **extra)
        if flag is not None:
            # Skipped step: zero updates, optimizer state held; it
            # discards the gradient, so the residual computed from it
            # must not carry either.
            updates = _nf.select_on_flag(
                flag, jax.tree.map(jnp.zeros_like, updates), updates
            )
            new_state = _nf.select_on_flag(flag, state, new_state)
            if ef is not None:
                new_ef = _nf.select_on_flag(flag, ef, new_ef)
        return updates, close(new_state, new_ef)

    return _distributed(
        init_fn, update_fn, optimizer, op=op, axis_name=axis_name,
        fusion_threshold_bytes=caller_threshold, compression=compression,
        hierarchical=caller_hierarchical, quantized=caller_quantized,
        error_feedback=error_feedback,
        backward_passes_per_step=backward_passes_per_step, overlap=overlap,
        nonfinite=nonfinite, tuned=tuned, topo_algorithm=topo_algorithm,
    )


def broadcast_variables(
    variables: Any, mesh: Mesh, *, root_rank: int = 0, axis_name: str = DATA_AXIS
) -> Any:
    """Make every rank's copy of a replicated pytree identical to root's
    (parity with ``broadcast_global_variables`` /
    ``broadcast_parameters``). Inside a single-controller mesh the arrays
    are already globally consistent, so this is a sharding-constraint
    replication; under multi-controller it lowers to an ICI broadcast."""
    def body(tree):
        return jax.tree.map(
            lambda x: _c.broadcast(x, root_rank=root_rank, axis_name=axis_name), tree
        )

    fn = _shard_map(body, mesh, in_specs=(P(),), out_specs=P())
    return jax.jit(fn)(variables)


class _Placement(_NamedTuple):
    """Where a step's leaves live on the mesh: the step body reads this
    and never asks which mode it is in. Built from nothing (replicated)
    or from the rules table on the first call (composed). PartitionSpecs
    act as pytree prefixes; loss, aux and the abort flag leave replicated."""

    params: Any       # spec (tree) of params, in and out
    state: Any        # of the optimizer state, in and out
    batch: Any        # of the batch
    loss_axes: tuple  # loss and aux are pmean-ed over these, in turn
    flag_axes: Any    # the skip/abort flag is agreed over these
    zero1_lead: int   # stacked leading axes of a Zero1State row (0: none)


def _check_zero1_args(op, compression, topo_algorithm):
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"zero1=True shards the optimizer update over a summed "
            f"gradient; op must be SUM/AVERAGE, got {ReduceOp(op).name}"
        )
    if compression is not Compression.none:
        raise ValueError(
            "zero1=True reduce-scatters raw buckets; cast compression "
            "has no shard-image form — use quantized=True instead"
        )
    if topo_algorithm == "split":
        raise ValueError(
            "topo_algorithm='split' has no reduce-scatter decomposition; "
            "zero1 lowers flat or two-level by the mesh shape"
        )


def _plan_replicated(
    mesh: Mesh, *, axis_name, op, fusion_threshold_bytes, compression,
    hierarchical, quantized, error_feedback, overlap, first_bucket_bytes,
    nonfinite, topo_algorithm, zero1,
) -> tuple:
    """Argument checks of the step with replicated params, plain or
    ``zero1``. Under ``zero1`` the quantized wire is flat-axis only
    (DCN-only compression has no RS+AG form) and ``topo_algorithm`` pins
    nothing — the RS lowering is determined by the axis shape — except
    ``"split"``, which has no reduce-scatter form and raises. Returns the
    mode's decisions as data: ``(place(params, opt_state) -> _Placement,
    keywords of _build_step)``."""
    quantized = _resolve_quantized(quantized)
    _check_overlap_rejections(overlap, quantized, op)
    if quantized and compression is not Compression.none:
        raise ValueError(
            "quantized=True already compresses the wire to int8; "
            "stacking cast compression would add loss for no bandwidth win"
        )
    if zero1:
        _check_zero1_args(op, compression, topo_algorithm)
    # "auto": the mesh decides — a (pod,) cross, local hierarchy engages
    # per-bucket compositor plan selection (flat/two-level/split by
    # payload bytes, docs/topology.md); a flat data mesh stays flat. This
    # is what makes make_train_step(overlap=True) go hierarchical
    # automatically on multi-slice topologies.
    hierarchical, hier_axes = _resolve_hierarchical(hierarchical, mesh)
    if hierarchical == "planned" and hier_axes and axis_name == DATA_AXIS:
        axis_name = hier_axes
    axis_name = _normalize_axis(axis_name, hierarchical)
    if zero1 and quantized and not isinstance(axis_name, str):
        raise ValueError(
            "quantized zero1 runs the flat int8 ring reduce-scatter "
            "over ONE axis; hierarchical (DCN-only) compression is not "
            "defined for the RS+AG decomposition — drop hierarchical or "
            "quantized"
        )
    policy = _resolve_nonfinite(nonfinite)
    use_ef = _resolve_error_feedback(
        error_feedback, quantized, False if zero1 else hierarchical
    )
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    over = P(axes[0] if len(axes) == 1 else axes)
    n_shards = _math.prod(int(mesh.shape[a]) for a in axes) if zero1 else 0
    # A Zero1State shards its leading [n_shards] axis as the batch does.
    place = _Placement(
        params=P(), state=over if zero1 else P(), batch=over,
        loss_axes=(axis_name,), flag_axes=axis_name, zero1_lead=int(zero1),
    )
    return (lambda params, opt_state: place), dict(
        wire=dict(
            op=op, axis_name=axis_name, quantized=quantized, zero1=zero1,
            threshold_bytes=fusion_threshold_bytes,
            first_bucket_bytes=first_bucket_bytes,
            hierarchical=hierarchical, compression=compression,
            nonfinite=policy,
            # A pinned compositor algorithm only reaches the lowering in
            # planned mode; anywhere else (flat mesh, forced two-level,
            # zero1's reduce-scatter) it is moot.
            algorithm=(
                topo_algorithm
                if hierarchical == "planned" and not zero1 else None
            ),
        ),
        guard=dict(
            labels=("zero1", "zero1", "train_step"), pre_flag=False,
        ) if zero1 else dict(labels=("reduce", "overlap", "train_step")),
        use_ef=use_ef,
        abort_words=(
            "the zero1 update was not applied on any rank (cross-rank "
            "agreed)"
        ) if zero1 else (
            "the update was not applied on any rank (cross-rank agreed) — "
            "rolling back via the elastic layer if one is active"
        ),
        notes=dict(
            hierarchical=str(hierarchical),
            **(dict(zero1=True) if zero1 else {}),
        ),
        n_shards=n_shards,
        expects=(
            "zero1=True expects the sharded Zero1State from "
            "hvd.init_zero1_stream_state(optimizer, params, "
            f"{n_shards}, ...)"
        ),
    )


def init_composed_zero1_state(
    optimizer,
    params,
    rules: Any,
    mesh: Mesh,
    *,
    model_axis: str = "model",
    axis_name: Any = DATA_AXIS,
    threshold_bytes: Optional[int] = None,
    first_bucket_bytes: Optional[int] = None,
    quantized: bool = False,
):
    """:class:`Zero1State` for ``make_train_step(rules=..., zero1=True)``:
    per MODEL rank, the streamed per-bucket state of that rank's local
    param shards (``parallel/rules.local_shard_tree`` slices them), with
    the per-bucket stacks laid out ``[n_data, n_model, ...]`` — shard
    the leading two axes ``P(data, model)``; the step indexes its
    ``[0, 0]`` cell. The bucket partition is over each model rank's
    LOCAL leaves, so it round-trips bitwise with the in-step update.
    Composed mode carries no EF residual (the sharded-EF side channel is
    a single-axis feature); the int8 wire still applies per DP bucket."""
    from ..parallel import rules as _rules

    rules = _rules.resolve_rules(rules)
    specs = _rules.match_partition_rules(rules, params)
    n_model = int(mesh.shape[model_axis])
    n_data = _math.prod(int(mesh.shape[ax]) for ax in (
        axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    ))
    states = []
    for m in range(n_model):
        local = _rules.local_shard_tree(
            params, specs, {model_axis: (m, n_model)}
        )
        states.append(init_zero1_stream_state(
            optimizer, local, n_data,
            threshold_bytes=threshold_bytes,
            first_bucket_bytes=first_bucket_bytes,
            quantized=quantized, error_feedback=False,
        ))
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=1), *states)


def _plan_composed(
    mesh: Mesh, *, rules, model_axis, tp_overlap, axis_name, op,
    fusion_threshold_bytes, compression, hierarchical, quantized,
    error_feedback, overlap, first_bucket_bytes, nonfinite, topo_algorithm,
    zero1,
) -> tuple:
    """Argument checks of the composed step (sharded leaves enter as
    local shards; ONE forward psum per Megatron half-block).
    Replicated-leaf gradients come out of the backward already FULL and
    model-identical — ``parallel/tp.py``'s f/g conjugate psums
    (``tp_block_input`` + ``row_parallel``) reduce the cotangents at
    every replicated->sharded boundary — so the DP reduction is the only
    gradient collective this step adds. Composed mode carries no EF
    residual (the sharded-EF side channel is a single-axis feature)."""
    from ..parallel import rules as _rules
    from ..parallel import tp as _tp

    rules = _rules.resolve_rules(rules)
    # The DP scope may itself be hierarchical — an explicit
    # ("cross", "local") axis TUPLE runs the zero1 RS/AG through the
    # compositor's two-level lowerings, still strictly on the data
    # axes. The model axis stays a single flat ICI axis.
    dp_axes = (
        tuple(axis_name) if isinstance(axis_name, (tuple, list))
        else (axis_name,)
    )
    for ax in dp_axes + (model_axis,):
        if ax not in mesh.axis_names:
            raise ValueError(
                f"composed mode needs mesh axes ({axis_name!r}, "
                f"{model_axis!r}); mesh has {tuple(mesh.axis_names)}"
            )
    if model_axis in dp_axes:
        raise ValueError(
            f"model_axis {model_axis!r} cannot also be a data axis"
        )
    axis_name = dp_axes[0] if len(dp_axes) == 1 else dp_axes
    if hierarchical == "auto":
        hierarchical = False  # the explicit axis tuple IS the hierarchy
    if hierarchical:
        raise ValueError(
            "composed rules= mode scopes hierarchy to the DP axes "
            "EXPLICITLY: pass axis_name=('cross', 'local') for a "
            "two-level DP scope instead of hierarchical=True — the TP "
            "psums must never be re-planned onto DCN, so the knob that "
            "re-plans the whole step is rejected"
        )
    if topo_algorithm is not None:
        raise ValueError(
            "topo_algorithm pins a compositor plan; the composed DP "
            "axis lowers flat and TP psums are never re-planned — drop "
            "topo_algorithm"
        )
    if compression is not Compression.none:
        raise ValueError(
            "composed mode rejects cast compression; use "
            "quantized=True for the DP-axis int8 wire"
        )
    if error_feedback:
        raise ValueError(
            "error feedback rides the single-axis streamed side "
            "channel; composed mode runs the int8 wire EF-off"
        )
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"composed mode reduces SUM/AVERAGE over the data axis; "
            f"got {ReduceOp(op).name}"
        )
    quantized = _resolve_quantized(quantized)
    _check_overlap_rejections(overlap, quantized, op)
    if quantized and len(dp_axes) > 1:
        raise ValueError(
            "quantized composed DP runs the flat int8 ring over ONE "
            "data axis; the two-level DP scope has no int8 RS+AG form "
            "— drop quantized or the axis tuple"
        )
    policy = _resolve_nonfinite(nonfinite)
    n_data = _math.prod(int(mesh.shape[ax]) for ax in dp_axes)

    def place(params, opt_state):
        # The Pass 5 preflight validates the live params' spec tree
        # ALWAYS — not gated on HOROVOD_TPU_STATIC_CHECKS; the same rule
        # table matches the optimizer state (optax trees embed the param
        # names); a composed Zero1State is stacked [n_data, n_model, ...].
        _rules.preflight_rules(rules, mesh, params)
        specs = _rules.match_partition_rules(rules, params)
        if not zero1:
            state_spec = _rules.match_partition_rules(rules, opt_state)
        elif isinstance(opt_state, Zero1State):
            state_spec = P(axis_name, model_axis)
        else:
            raise TypeError(
                "composed zero1=True expects the Zero1State from "
                "hvd.init_composed_zero1_state(optimizer, params, "
                f"rules, mesh, ...); got {type(opt_state).__name__}"
            )
        return _Placement(
            params=specs, state=state_spec, batch=P(axis_name),
            loss_axes=(axis_name, model_axis),
            # Agreement over EVERY axis: a model rank's NaN must skip
            # the step on every rank of the whole mesh.
            flag_axes=dp_axes + (model_axis,),
            zero1_lead=2 if zero1 else 0,
        )

    return place, dict(
        wire=dict(
            op=op, axis_name=axis_name, quantized=quantized, zero1=zero1,
            threshold_bytes=fusion_threshold_bytes,
            first_bucket_bytes=first_bucket_bytes, nonfinite=policy,
        ),
        guard=dict(
            labels=("composed", "composed", "composed"),
            fused_label="composed-posthoc",
        ),
        use_ef=False,
        abort_words=(
            "the composed update was not applied on any rank (cross-rank "
            "agreed over data AND model axes)"
        ),
        notes=dict(
            composed=True, tp=int(mesh.shape[model_axis]), dp=n_data,
            tp_overlap=_tp.tp_overlap_enabled(tp_overlap), zero1=zero1,
        ),
        n_shards=n_data,
        # Pin the TP-path selection for the trace: tp_apply (and any
        # user loss built on parallel/tp.py) consults
        # tp.overlap_active() so `tp_overlap=True` here reaches the
        # model without threading a flag through user code. None keeps
        # HOROVOD_TP_OVERLAP in charge. The step runs unchecked (the
        # bucket-fused DP reduction cannot be typed by the vma checker);
        # the TP layers see that and write their f/g conjugates out as
        # custom VJPs.
        loss_scope=lambda: _tp.overlap_scope(tp_overlap),
    )


def _loss_and_grads(loss_fn, params, ef, batch, *, has_aux: bool,
                    stream: Optional[dict], loss_scope: Callable):
    """Differentiate the user loss on this rank's shard, under
    ``SCOPE_LOSS_GRAD``: ``(out, grads, new_ef)``. ``stream`` registers
    the params' children for streamed reduction; with a residual ``ef``
    as well, differentiating w.r.t. the residual is the EF side channel:
    the streamed backward rule returns the NEXT residual as ef's
    "gradient" (ops/fusion.py)."""
    streamed_ef = stream is not None and ef is not None

    def local_loss(p, e, b):
        if stream is not None:
            p = _fusion.stream_param_groups(
                p, **stream, **({"ef": e} if streamed_ef else {})
            )
        with loss_scope():
            return loss_fn(p, b)

    grad_fn = jax.value_and_grad(
        local_loss, argnums=(0, 1) if streamed_ef else 0, has_aux=has_aux
    )
    with jax.named_scope(_trace.SCOPE_LOSS_GRAD):
        out, grads = grad_fn(params, ef, batch)
    new_ef = ef
    if streamed_ef:
        grads, new_ef = grads
    if stream is not None:
        # Consume the registration ledger so a later overlap
        # DistributedOptimizer trace doesn't credit THIS trace's
        # registrations.
        _fusion.take_stream_registrations()
    return out, grads, new_ef


def _optax_update(optimizer, params, opt_state, grads):
    import optax

    updates, new_opt_state = optimizer.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), new_opt_state


def _build_step(
    loss_fn, optimizer, mesh: Mesh, place: _Placement, *, donate: bool,
    has_aux: bool, overlap: bool, wire: dict, guard: dict, use_ef: bool,
    abort_words: str, notes: dict, n_shards: int = 0, expects: str = "",
    loss_scope: Callable = _contextlib.nullcontext,
):
    """The one train-step body and its launch wrapper: differentiate,
    exchange under the guard, update, hold everything on a skipped step.
    A mode's argument checks hand in its decisions as data: ``wire`` is
    what ``stream_param_groups`` AND ``_exchange_posthoc`` take,
    ``guard`` the latter's own (labels, pre_flag, fused_label),
    ``n_shards`` the rows of a Zero1State and ``expects`` what a state
    that is none is told. An ``optimizer`` that is a
    ``DistributedOptimizer`` is opened: this body's exchange is the
    step's only one (its options were merged into ``wire`` by
    ``_adopt_wrapper_options``), and the update goes through the
    optimizer it wraps, after the wrapper's ``1 /
    backward_passes_per_step``. Returns ``(step, jitted)``: what the
    caller runs, and the inner ``jax.jit`` function (HLO inspection)."""
    policy, quantized = wire["nonfinite"], wire["quantized"]
    abort = policy == "abort"
    prescale, wrapper = 1.0, "none"
    if isinstance(optimizer, _distributed_transformation()):
        wrapper = "DistributedOptimizer"
        prescale = 1.0 / optimizer.options.get("backward_passes_per_step", 1)
        optimizer = optimizer.wrapped
    # One signature, (optimizer, params, state, grads) -> (params, state):
    # the optax update, or the shard-local one on the un-stacked
    # Zero1State row.
    update = _optax_update
    if wire["zero1"]:
        update = _functools.partial(
            zero1_stream_update, n_shards=n_shards,
            axis_name=wire["axis_name"], quantized=quantized,
            threshold_bytes=wire["threshold_bytes"],
            first_bucket_bytes=wire["first_bucket_bytes"],
        )

    def average(x):
        return _functools.reduce(lax.pmean, place.loss_axes, x)

    def step(params, opt_state, batch):
        inner, ef, close = _open_state(
            opt_state, params, use_ef=use_ef,
            zero1_lead=place.zero1_lead, expects=expects,
        )
        out, grads, new_ef = _loss_and_grads(
            loss_fn, params, ef, batch, has_aux=has_aux,
            stream=wire if overlap else None, loss_scope=loss_scope,
        )
        loss, aux = out if has_aux else (out, None)
        grads, new_ef, flag = _exchange_posthoc(
            grads, new_ef, reduce=not overlap, flag_axes=place.flag_axes,
            **wire, **guard,
        )
        loss = average(loss)
        with jax.named_scope(_trace.SCOPE_OPTIMIZER):
            if prescale != 1.0:
                grads = jax.tree.map(lambda g: g * prescale, grads)
            new_params, new_inner = update(optimizer, params, inner, grads)
        if flag is not None:
            # Skipped step: params and optimizer state held on EVERY
            # rank; it discards the gradient, so the residual computed
            # from it must not carry either.
            new_params = _nf.select_on_flag(flag, params, new_params)
            new_inner = _nf.select_on_flag(flag, inner, new_inner)
            if ef is not None:
                new_ef = _nf.select_on_flag(flag, ef, new_ef)
        outs = [new_params, close(new_inner, new_ef), loss]
        if has_aux:
            outs.append(jax.tree.map(average, aux))
        if abort:
            outs.append(flag)
        return tuple(outs)

    fn = _shard_map(
        step, mesh,
        in_specs=(place.params, place.state, place.batch),
        out_specs=(place.params, place.state)
        + (P(),) * (1 + int(has_aux) + int(abort)),
    )
    jitted = jax.jit(fn, donate_argnums=(0, 1) if donate else ())
    launch = jitted
    if abort:
        def aborting_step(params, opt_state, batch):
            import numpy as np

            out = jitted(params, opt_state, batch)
            flag = out[-1]
            if float(np.asarray(flag)) > 0:
                from .. import HorovodInternalError

                if _trace.ACTIVE:
                    # Flight recorder: the abort is about to unwind into
                    # the elastic rollback — persist the last moments
                    # first.
                    _trace.TAP.flight_dump("guard-abort")
                raise HorovodInternalError(
                    "non-finite gradient guard (policy abort): a rank "
                    "produced NaN/Inf gradients this step; "
                    + abort_words
                )
            return out[:-1]

        launch = aborting_step
    # Fleet-tracing step tap (docs/timeline.md "Step spans"): host-side
    # step-boundary timestamps + step index, stamped with the build-time
    # correlation ids so one trace links step → bucket → collective →
    # hop. NULL_TAP discipline: disabled → the function is returned
    # UNCHANGED (wrap_step(f) is f).
    return _trace.wrap_step(
        launch, overlap=overlap, quantized=quantized,
        wire_dtype="int8" if quantized else "f32",
        op=ReduceOp(wire["op"]).name, nonfinite=policy, grad_exchanges=1,
        optimizer_wrapper=wrapper, **notes,
    ), jitted


def _fill_from_tuned(kwargs: dict, tunable, tuned_cfg, tuned_source: str,
                     params, mesh: Mesh, where: str) -> dict:
    """One build's keywords after the tuned file (``make_train_step``'s
    ``tuned``): where its key matches the live signature, each knob of
    ``tunable`` that the caller left at its default takes the pinned
    value."""
    from .. import tune as _tune

    live = _tune.step_signature(params, mesh=mesh)
    matched = _tune.signatures_match(tuned_cfg.signature, live)
    kw = dict(kwargs)
    if matched:
        tk = _tune.tuned_step_kwargs(tuned_cfg)
        for name in tunable:
            # "left alone" is None, or False for the tri-state hierarchical
            if kw[name] is (False if name == "hierarchical" else None):
                kw[name] = tk[name]
        if kw["zero1"] and kw["topo_algorithm"] == "split":
            # No reduce-scatter decomposition of the FlexLink split
            # exists; the zero1 lowering is decided by the mesh shape —
            # fall back to per-bucket selection.
            kw["topo_algorithm"] = None
    else:
        _tune.warn_signature_mismatch(
            tuned_cfg, live.get("hash", "?"), where
        )
    _tune.note_applied(
        tuned_source, tuned_cfg.signature_hash, matched, where
    )
    return kw


def _adopt_wrapper_options(kwargs: dict, wrapper) -> dict:
    """``make_train_step``'s keywords after the ``DistributedOptimizer``
    it was handed. The step reduces the gradients ONCE, so that exchange
    takes each option the wrapper states where the step left it at its
    default; an option both state, differently, raises."""
    import inspect

    defaults = inspect.signature(make_train_step).parameters
    kw = dict(kwargs)
    for name in sorted(wrapper.stated & set(kwargs)):
        theirs, mine = wrapper.options[name], kwargs[name]
        if mine == defaults[name].default:
            kw[name] = theirs
        elif mine != theirs:
            raise ValueError(
                f"make_train_step({name}={mine!r}) was handed a "
                f"DistributedOptimizer({name}={theirs!r}): the step "
                f"reduces the gradients once, so state {name} once (or "
                "the same on both)"
            )
    return kw


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    op: ReduceOp = Average,
    fusion_threshold_bytes: Optional[int] = None,
    compression=Compression.none,
    hierarchical: Any = False,
    quantized: Optional[bool] = None,
    error_feedback: Optional[bool] = None,
    donate: bool = True,
    has_aux: bool = False,
    overlap: bool = False,
    first_bucket_bytes: Optional[int] = None,
    nonfinite: Optional[str] = None,
    tuned: Any = None,
    topo_algorithm: Optional[str] = None,
    zero1: bool = False,
    rules: Any = None,
    model_axis: str = "model",
    tp_overlap: Optional[bool] = None,
):
    """Build a jitted SPMD training step: per-shard grads → fused allreduce
    → optax update, with the batch sharded over ``axis_name`` and
    params/opt-state replicated.

    ``loss_fn(params, batch) -> loss`` (or ``(loss, aux)`` with
    ``has_aux=True``; aux leaves are pmean-averaged) is evaluated on each
    rank's local shard; gradient reduction uses the configured
    op/compression — the whole reference ``DistributedOptimizer`` pipeline
    as one XLA program. With ``hierarchical=True`` the mesh must have
    (cross, local) axes (see ``build_hierarchical_mesh``).

    ``optimizer`` may be a :func:`DistributedOptimizer` (the quick
    start's form): the step opens it, reduces the gradients ONCE and
    updates through the optimizer it wraps, keeping the wrapper's
    ``1 / backward_passes_per_step``; bare ``tx`` and
    ``DistributedOptimizer(tx)`` build the same program. An exchange
    option stated on the wrapper and left at its default here
    (``op``, ``compression``, ``quantized``, ``error_feedback``,
    ``hierarchical``, ``fusion_threshold_bytes``, ``topo_algorithm``,
    ``nonfinite``, ``overlap``, ``axis_name``, ``tuned``, ``zero1``)
    applies to that one exchange; stated on both sides with different
    values it raises a ``ValueError`` that names the keyword and both
    values. A wrapper inside ``optax.chain(...)`` is not seen: it
    reduces again inside the update, as any opaque transformation would.

    ``overlap=True`` switches from the post-hoc whole-tree reduction to the
    streamed path (docs/overlap.md): the top-level children of ``params``
    are packed into DDP-style reverse-order groups (a smaller first bucket,
    ``first_bucket_bytes`` / HOROVOD_FUSION_FIRST_BUCKET_BYTES) and each
    group's psums are issued INSIDE the backward pass as soon as that
    group's gradients exist — independent collectives XLA can overlap with
    the remaining backward compute. Numerically identical to
    ``overlap=False`` (elementwise reductions commute with the split).

    ``quantized=True`` (None reads ``HOROVOD_QUANTIZED_WIRE``) moves each
    gradient bucket over the int8 wire (``ops/quantized.py``) — composed
    with ``overlap=True`` the quantize→ring-reduce→dequantize runs inside
    the backward trace per streamed bucket, preserving the
    scheduler-overlap property; composed with ``hierarchical`` only the
    outermost (DCN) hop is compressed. On the flat wire an error-feedback
    residual (``error_feedback``, default on; EF-SGD) rides the optimizer
    state: the step accepts a plain ``optimizer.init(params)`` opt_state
    and returns ``EFState(inner=..., residual=...)`` from the first call
    on (or start from :func:`error_feedback_state` for a stable
    structure, e.g. under ``lax.scan``).

    ``nonfinite`` (None reads ``HOROVOD_GUARD_NONFINITE``, resolved when
    the step is built) applies the non-finite gradient guard around the
    reduce: ``zero`` sanitizes before the wire (per streamed group under
    ``overlap=True``), ``warn`` logs detections, ``skip`` cross-rank
    agrees on a skip flag and leaves params/opt-state UNCHANGED on every
    rank for that step, ``abort`` additionally raises
    ``HorovodInternalError`` from the returned step function so the
    elastic layer rolls back — docs/fault_tolerance.md "Data-plane
    integrity".

    ``rules`` (docs/parallelism.md "Composed DP x TP fast path") switches
    to composed mode: a sharding-rules table (a ``(regex,
    PartitionSpec)`` sequence or a shipped name like ``"gpt"`` —
    ``parallel/rules.py``) places params and optimizer state on the
    ``(axis_name, model_axis)`` mesh, ``loss_fn`` runs on the LOCAL
    shards calling ``parallel/tp.py`` layers bound to ``model_axis``
    (e.g. ``models.transformer.tp_apply``), and the whole
    overlap/quantized/zero1 reduction stack applies to the DATA axis
    only — TP psums are never bucketized, quantized, or re-planned. The
    build is then deferred to the first call, whose live trees the
    tables are matched against.
    ``tp_overlap=True`` (default: the ``HOROVOD_TP_OVERLAP`` knob)
    additionally routes the TP layers through the chunked
    collective-matmul primitives (docs/parallelism.md "Fused TP
    overlap"): the residual stream token-shards over ``model_axis`` and
    the block psums dissolve into bidirectional ppermute chains
    overlapped with the matmuls — zero model-axis all-reduces in the
    step's HLO. ``zero1=True`` then takes the state from
    :func:`init_composed_zero1_state`. The returned step exposes
    ``step.sharding_specs`` (after the first call) for the guard's
    digest agreement (``guard/digest.strip_rank_local``).

    ``zero1=True`` (docs/overlap.md "Streamed ZeRO-1") shards the
    optimizer state per streamed bucket over the data axis: the step
    takes the :class:`Zero1State` from :func:`init_zero1_stream_state`
    (built with the SAME threshold/first-bucket/quantized knobs),
    reduce-scatters each gradient bucket, and runs the shard-local optax
    update and parameter all-gather against the same bucket plan
    (``parallel/zero.zero1_stream_update``). Under ``overlap=True`` each
    bucket reduce-scatters INSIDE the backward trace — each rank keeps
    only its shard's cotangents, (n-1)/n of the gradient payload rides
    the wire, and the scheduler hides it behind the remaining backward
    compute; ``overlap=False`` runs the identical per-bucket reduction
    post-hoc (bitwise-equal, zero overlap).
    Composes with ``quantized=True`` (int8 ring RS, the error-feedback
    residual carried SHARDED in the ``Zero1State``) and
    ``hierarchical="auto"`` (on a multi-slice mesh each bucket's RS/AG
    lowers via the compositor's two-level schedules: only the 1/L shard
    crosses DCN); a matching ``tuned`` config fills the same knobs it
    fills for the allreduce paths.

    ``tuned`` (pinned offline tuning, docs/autotune.md "Compiled-path
    offline tuning") takes a ``tuned.json`` path, a
    :class:`horovod_tpu.tune.TunedConfig`, ``None`` (read
    ``HOROVOD_TUNED_FILE``), or ``False`` (explicitly untuned). With a
    tuning in hand the step build is deferred to the FIRST call: the
    live params' abstract signature (pytree structure + leaf
    shapes/dtypes + mesh axes) is compared against the tuning's key —
    on a match the pinned knobs fill every knob the caller left at its
    default (explicit arguments always win); on a mismatch a loud
    warning is logged and the step builds with untuned defaults, never
    with stale knobs. The applied source lands in ``hvd_tuned_info``
    (docs/metrics.md) and in eager plan verdicts
    (``core/xla_executor.py``).

    A tuned build is bitwise-identical to passing the same knob values
    by hand — ``horovod_tpu.tune.tuned_step_kwargs`` is the exact
    mapping, asserted by ``make tune-smoke``.
    """
    from .. import tune as _tune

    kwargs = dict(
        axis_name=axis_name, op=op,
        fusion_threshold_bytes=fusion_threshold_bytes,
        compression=compression, hierarchical=hierarchical,
        quantized=quantized, error_feedback=error_feedback,
        overlap=overlap, first_bucket_bytes=first_bucket_bytes,
        nonfinite=nonfinite, topo_algorithm=topo_algorithm, zero1=zero1,
        tuned=tuned,
    )
    shards = None
    if isinstance(optimizer, _distributed_transformation()):
        kwargs = _adopt_wrapper_options(kwargs, optimizer)
        overlap, zero1 = kwargs["overlap"], kwargs["zero1"]
        shards = optimizer.options.get("zero1_shards")
    tuned_cfg, tuned_source = _tune.resolve_tuned(kwargs.pop("tuned"))
    tunable = ("fusion_threshold_bytes", "first_bucket_bytes")
    if rules is not None:
        plan_of = _functools.partial(
            _plan_composed, mesh, rules=rules, model_axis=model_axis,
            tp_overlap=tp_overlap,
        )
        where = "make_train_step(rules=...)"
        # Composed mode rejects its arguments at the call; only the
        # placement waits for the live trees.
        plan_of(**kwargs)
    elif tp_overlap is not None:
        raise ValueError(
            "tp_overlap selects the fused collective-matmul TP path of "
            "the composed builder — pass rules=... (and a model axis); "
            "without tensor parallelism there is no TP psum to fuse"
        )
    else:
        plan_of = _functools.partial(_plan_replicated, mesh)
        where = "make_train_step"
        # composed mode rejects these, so a tuned file may not fill them
        tunable += ("quantized", "hierarchical", "topo_algorithm")

    def build(params=None, opt_state=None):
        kw = kwargs
        if tuned_cfg is not None:
            kw = _fill_from_tuned(
                kwargs, tunable, tuned_cfg, tuned_source, params, mesh, where
            )
        place_of, step_kw = plan_of(**kw)
        if shards is not None and shards != step_kw["n_shards"]:
            raise ValueError(
                f"DistributedOptimizer(zero1_shards={shards}) built its "
                f"state for {shards} shards; the mesh's data axis has "
                f"{step_kw['n_shards']}"
            )
        place = place_of(params, opt_state)
        return _build_step(
            loss_fn, optimizer, mesh, place, donate=donate,
            has_aux=has_aux, overlap=overlap, **step_kw,
        ) + (place,)

    if rules is None and tuned_cfg is None:
        return build()[0]

    # One deferred first-call build for both needs: a tuned file to match
    # against the live signature, rules to match against the live trees.
    built: dict = {}

    def dispatch(params, opt_state, batch):
        if "step" not in built:
            built["step"], jitted, place = build(params, opt_state)
            # The inner jax.jit step — HLO inspection (tests assert the
            # one-psum-per-block TP structure off it).
            dispatch.jitted = jitted
            if rules is not None:
                # Digest integration (guard/digest.strip_rank_local): the
                # spec trees mark which leaves are TP-sharded — attach as
                # State.sharding_specs so cross-rank digests hash their
                # LAYOUT, never their (legitimately divergent) bytes.
                dispatch.sharding_specs = {
                    "params": place.params,
                    **({} if zero1 else {"opt_state": place.state}),
                }
        return built["step"](params, opt_state, batch)

    dispatch.sharding_specs = dispatch.jitted = None
    return dispatch


def make_decode_step(
    *,
    n_heads: int,
    mesh: Optional[Mesh] = None,
    rules: Any = None,
    cache_rules: Any = None,
    model_axis: str = "model",
    dtype: Any = jnp.float32,
):
    """Build the compiled batched one-token greedy-decode step for
    ``hvd.serve()`` (docs/serving.md):

        step(params, cache, tokens, positions, page_table)
            -> (next_tokens [B] int32, new_cache)

    ``cache`` is the paged decode-state pytree
    (``serve/kvcache.make_decode_state``) and ``page_table`` the [B,
    max_pages] slot→page map; the forward is
    ``models/transformer.tp_decode_apply`` — the same param tree the
    composed train step shards, consumed as TP-local shards with ONE
    psum per Megatron half-block.

    With ``mesh`` + ``rules`` the step is shard_mapped: params placed by
    the rule table and the cache by ``cache_rules`` (default
    ``parallel/rules.GPT_CACHE_RULES`` — head dim over ``model_axis``),
    BOTH preflighted by the Pass 5 validator against the live trees
    before anything is traced, the composed-path discipline.
    tokens/positions/page_table replicate: data parallelism in serving
    is ENGINE-level (each DP replica runs its own step on its own
    batches — ``serve/engine.py``), not a mesh axis of the decode step.
    With ``mesh=None`` it is the dense single-chip reference the parity
    tests compare against the full-recompute :func:`tp_apply`.

    The build is deferred to the first call: the live params + cache
    decide the spec trees.
    """
    from ..models.transformer import tp_decode_apply
    from ..parallel import rules as _rules

    if (mesh is None) != (rules is None):
        raise ValueError(
            "make_decode_step shards by TABLE: pass mesh= and rules= "
            "together (or neither for the dense reference)"
        )
    if mesh is not None and model_axis not in mesh.axis_names:
        raise ValueError(
            f"decode mesh needs axis {model_axis!r}; mesh has "
            f"{tuple(mesh.axis_names)}"
        )

    built: dict = {}

    def _build(params, cache):
        if mesh is None:
            def step(params, cache, tokens, positions, page_table):
                logits, new_cache = tp_decode_apply(
                    params, tokens, positions, cache, page_table,
                    n_heads=n_heads, model_axis=None, dtype=dtype,
                )
                next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return next_tokens, new_cache

            return jax.jit(step)

        crules = (
            _rules.GPT_CACHE_RULES if cache_rules is None
            else _rules.resolve_rules(cache_rules)
        )
        resolved = _rules.resolve_rules(rules)
        # Pass 5 preflight over BOTH tables — always enforced.
        _rules.preflight_rules(resolved, mesh, params)
        _rules.preflight_rules(crules, mesh, cache)
        specs = _rules.match_partition_rules(resolved, params)
        cache_specs = _rules.match_partition_rules(crules, cache)

        def step(params, cache, tokens, positions, page_table):
            logits, new_cache = tp_decode_apply(
                params, tokens, positions, cache, page_table,
                n_heads=n_heads, model_axis=model_axis, dtype=dtype,
            )
            next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tokens, new_cache

        fn = _shard_map(
            step, mesh, check=True,
            in_specs=(specs, cache_specs, P(), P(), P()),
            out_specs=(P(), cache_specs),
        )
        return jax.jit(fn)

    def dispatch(params, cache, tokens, positions, page_table):
        if "step" not in built:
            built["step"] = _build(params, cache)
        return built["step"](params, cache, tokens, positions, page_table)

    return dispatch


class GradientAccumulator:
    """Local gradient accumulation helper — parity with
    ``backward_passes_per_step`` (``horovod/torch/__init__.py:110-150``):
    accumulate ``n`` microbatch gradients locally, then allreduce once."""

    def __init__(self, n: int):
        self.n = n

    def init(self, grads: Any) -> Any:
        return jax.tree.map(jnp.zeros_like, grads)

    def add(self, acc: Any, grads: Any) -> Any:
        return jax.tree.map(jnp.add, acc, grads)

    def should_reduce(self, step_count: int) -> bool:
        return (step_count + 1) % self.n == 0


_trace.note_import(_IMPORT_START, _time.perf_counter())
