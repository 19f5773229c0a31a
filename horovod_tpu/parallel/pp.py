"""Pipeline (stage) parallelism: GPipe-style microbatch pipelining over a
``stage`` mesh axis.

TPU-native extension beyond the reference framework (which is
model-agnostic DP only, SURVEY.md §2.3): each device owns one pipeline
stage's parameters; microbatches flow stage -> stage over ``lax.ppermute``
inside a ``lax.scan`` of n_micro + n_stages - 1 ticks (fill + steady +
drain). Because the whole schedule is traced functional code, jax autodiff
derives the backward pipeline (cotangents flow through the ppermute
transpose in the reverse direction) — no hand-written 1F1B schedule is
needed for correctness, and XLA overlaps each tick's compute with the
next's ICI transfer.

Two APIs:

- ``make_pp_train_step`` — homogeneous stages: parameters enter with a
  leading [n_stages, ...] dim placed ``P(stage)``; every stage maps one
  activation shape to itself.
- ``make_pp_lm_train_step`` — heterogeneous ends as first-class stages:
  ``embed_fn`` ingests raw tokens on stage 0, ``head_loss_fn`` folds the
  projection + loss on the last stage, and only the hidden activation
  crosses ICI. ``remat=True`` bounds backward memory to the carried
  activations plus one rematerialized tick (``jax.checkpoint`` per tick
  — the memory role of 1F1B, scheduled by the compiler).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..common.compat import axis_size as _axis_size
from .mesh import DATA_AXIS

STAGE_AXIS = "stage"


def _zeros_with_vma_of(shape, dtype, ref):
    """Zeros of (shape, dtype) carrying ``ref``'s varying-axis type: a
    scan carry must match its body output's vma over every bound axis,
    including axes whose names the callee does not know. The dead
    multiply is DCE'd by XLA."""
    return jnp.zeros(shape, dtype) + jnp.zeros((), dtype) * ref.ravel()[
        0
    ].astype(dtype)


def _pvary(x, axis_name):
    """Mark a replicated value as device-varying over ``axis_name`` (vma
    bookkeeping only — the values are unchanged). Needed so the pipeline
    scan's carry has a consistent varying type across iterations."""
    return lax.pcast(x, (axis_name,), to="varying")


def _gpipe_scan(axis_name, n_micro, feed, stage_apply, emit, emit0):
    """The one GPipe fill/steady/drain scan both pipeline APIs share.

    - ``feed(i) -> h``: stage 0's input for microbatch i (raw slice or
      embedded tokens);
    - ``stage_apply(h, s) -> h``: this stage's compute;
    - ``emit(outs, idx, y, is_emit) -> outs``: fold the last stage's
      result for microbatch ``idx`` into the accumulator (tensor slot or
      per-microbatch loss).

    Ticks run n_micro + n_stages - 1 times; stage 0 ingests microbatch t
    (clamped past the end: the garbage never reaches an emit slot), the
    last stage emits microbatch t - (n_stages - 1), and each tick's
    output moves one hop down the line over ppermute (stage n-1's hop is
    dropped by the permutation — it exits via ``emit``).
    """
    s = lax.axis_index(axis_name)
    n_stages = _axis_size(axis_name)
    ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]
    state0 = jnp.zeros_like(feed(jnp.int32(0)))

    def tick(carry, t):
        state, outs = carry
        x_in = jnp.where(s == 0, feed(jnp.minimum(t, n_micro - 1)), state)
        y = stage_apply(x_in, s)
        out_idx = t - (n_stages - 1)
        idx = jnp.clip(out_idx, 0, n_micro - 1)
        is_emit = jnp.logical_and(s == n_stages - 1, out_idx >= 0)
        outs = emit(outs, idx, y, is_emit)
        state_next = lax.ppermute(y, axis_name, perm)
        return (state_next, outs), None

    (_, outs), _ = lax.scan(tick, (state0, emit0), jnp.arange(ticks))
    return outs


def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    x_micro: jax.Array,
    *,
    axis_name: str = STAGE_AXIS,
) -> jax.Array:
    """Run microbatches through the pipeline; call inside shard_map.

    ``stage_fn(params, x, stage_index)`` maps [mb, ...] -> [mb, ...] with
    this device's stage params; ``x_micro``: [n_micro, mb, ...] (the full
    input, present on every stage — stage 0 ingests it). Returns the last
    stage's outputs [n_micro, mb, ...] (zeros elsewhere; the caller
    typically psums or masks by stage).
    """
    x_micro = _pvary(x_micro, axis_name)

    def emit(outs, idx, y, is_emit):
        prev = lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            outs, jnp.where(is_emit, y, prev), idx, 0
        )

    return _gpipe_scan(
        axis_name, x_micro.shape[0],
        lambda i: x_micro[i],
        lambda h, s: stage_fn(stage_params, h, s),
        emit, jnp.zeros_like(x_micro),
    )


def make_pp_train_step(
    loss_fn: Callable,
    stage_fn: Callable,
    optimizer,
    mesh: Mesh,
    *,
    stage_axis: str = STAGE_AXIS,
    data_axis: str = DATA_AXIS,
    donate: bool = True,
):
    """Build a jitted DP×PP train step.

    ``stage_fn(params, x, stage_index)``: one stage's forward.
    ``loss_fn(y_micro, labels_micro) -> scalar``: loss on the pipeline
    output (runs on the last stage's values; every stage computes it on
    the psum-broadcast outputs so the graph stays SPMD).

    Params enter stacked [n_stages, ...] placed P(stage). Batches are
    PRE-SHAPED [n_micro, mb, ...]: dim 0 is the microbatch index
    (unsharded), dim 1 the per-microbatch batch, sharded over ``data`` and
    replicated across stages (in_specs P(None, data)).
    """
    from ..jax import _shard_map
    from ._stacked import stacked_train_update

    def step(params, opt_state, x_micro, y_micro):
        def local_loss(p):
            outs = pipeline_apply(
                stage_fn, p, x_micro, axis_name=stage_axis
            )
            # Outputs live on the last stage; share them so the loss (and
            # its gradient wiring) is SPMD-identical on every stage.
            n_stages = _axis_size(stage_axis)
            mask = (lax.axis_index(stage_axis) == n_stages - 1).astype(
                outs.dtype
            )
            outs = lax.psum(outs * mask, stage_axis)
            return loss_fn(outs, y_micro)

        params, opt_state, loss = stacked_train_update(
            optimizer, params, opt_state,
            jax.value_and_grad(local_loss), data_axis,
        )
        loss = lax.pmean(loss, data_axis)
        return params, opt_state, loss

    fn = _shard_map(
        step, mesh, check=True,
        in_specs=(P(stage_axis), P(stage_axis), P(None, data_axis),
                  P(None, data_axis)),
        out_specs=(P(stage_axis), P(stage_axis), P()),
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


from ._stacked import init_stacked_state  # noqa: E402

init_pp_state = init_stacked_state


# ---------------------------------------------------------------------------
# Heterogeneous pipelines: embed / body / head as first-class stages
# ---------------------------------------------------------------------------

def pipeline_lm_loss(
    embed_fn: Callable,
    stage_fn: Callable,
    head_loss_fn: Callable,
    embed_params: Any,
    stage_params_local: Any,
    head_params: Any,
    tokens_micro: jax.Array,
    labels_micro: jax.Array,
    *,
    axis_name: str = STAGE_AXIS,
    remat: bool = True,
) -> jax.Array:
    """Pipelined forward + loss with heterogeneous ends; call inside
    shard_map with ``axis_name`` bound.

    The wire between stages carries ONLY the hidden activation
    [mb, ...]: stage 0 ingests raw tokens through ``embed_fn`` and the
    last stage folds ``head_loss_fn`` (projection + loss) locally, so
    logits-sized tensors never cross ICI and callers no longer have to
    disguise embed/head as shape-preserving stages (the round-3
    homogeneous-pipeline constraint).

    - ``embed_fn(embed_params, tokens_mb) -> h``      [mb,...] any shape
    - ``stage_fn(stage_params, h, stage_idx) -> h``   shape-preserving
    - ``head_loss_fn(head_params, h, labels_mb) -> scalar``

    ``embed_params``/``head_params`` are replicated across the mesh; under
    a vma-checked shard_map their cotangents are psummed over the stage
    axis automatically, and only the owning stage's branch contributes
    (the ``where`` masks zero the rest), so the replicated update is
    exact. SPMD uniformity means every stage *computes* embed/head each
    tick and masks the result — for projection-dominated models put the
    head inside the last ``stage_fn`` or shard it with TP instead.

    ``remat=True`` wraps each tick's stage compute in ``jax.checkpoint``:
    the backward pass holds the carried activations plus ONE
    rematerialized tick instead of every tick's internals — the memory
    role of a 1F1B schedule, expressed through the compiler (the
    schedule itself stays GPipe fill/steady/drain; autodiff derives the
    reverse pipeline through the ppermute transpose).
    """
    tokens_micro = _pvary(tokens_micro, axis_name)
    labels_micro = _pvary(labels_micro, axis_name)
    n_micro = tokens_micro.shape[0]
    body = jax.checkpoint(stage_fn) if remat else stage_fn
    # The loss carry must inherit the inputs' varying-axis type over
    # EVERY bound axis — stage and the caller's data axis, whose name
    # this function cannot know, so _pvary alone is not enough; derive
    # it from a (DCE'd) embed evaluation instead.
    h_ref = embed_fn(embed_params, tokens_micro[0])
    losses0 = _zeros_with_vma_of((n_micro,), jnp.float32, h_ref)

    def emit(losses, idx, y, is_emit):
        mb_loss = head_loss_fn(
            head_params, y, labels_micro[idx]
        ).astype(jnp.float32)
        prev = lax.dynamic_index_in_dim(losses, idx, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            losses, jnp.where(is_emit, mb_loss, prev), idx, 0
        )

    losses = _gpipe_scan(
        axis_name, n_micro,
        lambda i: embed_fn(embed_params, tokens_micro[i]),
        lambda h, s: body(stage_params_local, h, s),
        emit, losses0,
    )
    # Losses live on the last stage; share so the value (and the gradient
    # wiring) is SPMD-identical everywhere.
    n_stages = _axis_size(axis_name)
    mask = (lax.axis_index(axis_name) == n_stages - 1).astype(losses.dtype)
    losses = lax.psum(losses * mask, axis_name)
    return losses.mean()


def init_pp_lm_state(optimizer, params):
    """Optimizer state for the heterogeneous layout: ``params`` is a dict
    {"embed", "stages" ([n_stages, ...]-stacked), "head"}; embed/head
    states are replicated like their params, stage states stacked."""
    return {
        "embed": optimizer.init(params["embed"]),
        "stages": init_stacked_state(optimizer, params["stages"]),
        "head": optimizer.init(params["head"]),
    }


def make_pp_lm_train_step(
    embed_fn: Callable,
    stage_fn: Callable,
    head_loss_fn: Callable,
    optimizer,
    mesh: Mesh,
    *,
    stage_axis: str = STAGE_AXIS,
    data_axis: str = DATA_AXIS,
    remat: bool = True,
    donate: bool = True,
):
    """Jitted DP x PP train step over a heterogeneous pipeline.

    ``step(params, opt_state, tokens_micro, labels_micro) ->
    (params, opt_state, loss)`` with ``params`` =
    {"embed", "stages", "head"} (see :func:`pipeline_lm_loss` /
    :func:`init_pp_lm_state`). Batches are [n_micro, mb, ...] with dim 1
    sharded over ``data``.
    """
    import optax

    from ..jax import _shard_map
    from ._stacked import apply_stacked_update

    def step(params, opt_state, tokens_micro, labels_micro):
        nd = _axis_size(data_axis)

        def loss_of(embed_p, stages_local, head_p):
            return pipeline_lm_loss(
                embed_fn, stage_fn, head_loss_fn,
                embed_p, stages_local, head_p,
                tokens_micro, labels_micro,
                axis_name=stage_axis, remat=remat,
            )

        stages_local = jax.tree.map(lambda t: t[0], params["stages"])
        loss, grads = jax.value_and_grad(loss_of, argnums=(0, 1, 2))(
            params["embed"], stages_local, params["head"]
        )
        # The vma-checked transpose already psummed each gradient over
        # every axis its parameter is invariant on (stage+data for
        # embed/head, data for stage params). Divide by the data size
        # to average.
        g_embed, g_stages, g_head = jax.tree.map(lambda g: g / nd, grads)

        new_params, new_state = {}, {}
        up, new_state["embed"] = optimizer.update(
            g_embed, opt_state["embed"], params["embed"]
        )
        new_params["embed"] = optax.apply_updates(params["embed"], up)
        new_params["stages"], new_state["stages"] = apply_stacked_update(
            optimizer, params["stages"], opt_state["stages"], g_stages
        )
        up, new_state["head"] = optimizer.update(
            g_head, opt_state["head"], params["head"]
        )
        new_params["head"] = optax.apply_updates(params["head"], up)
        return new_params, new_state, lax.pmean(loss, data_axis)

    pspec = {"embed": P(), "stages": P(stage_axis), "head": P()}
    fn = _shard_map(
        step, mesh, check=True,
        in_specs=(pspec, pspec, P(None, data_axis), P(None, data_axis)),
        out_specs=(pspec, pspec, P()),
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())
