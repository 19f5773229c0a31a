"""Shared plumbing for shard-stacked parameter layouts (TP's ``[n_model,
...]`` and PP's ``[n_stages, ...]`` leading dims placed over a mesh axis).
"""

from __future__ import annotations

from typing import Callable

import jax
from jax import lax

from ..common.compat import axis_size as _axis_size


def init_stacked_state(optimizer, params_stacked):
    """Optimizer state for [n, ...]-stacked params: one state per shard
    (vmapped init), so it places alongside the params' axis spec."""
    return jax.vmap(optimizer.init)(params_stacked)


def apply_stacked_update(optimizer, params, opt_state, grads_local):
    """Unstack -> optimizer.update on this shard's row -> restack.
    ``grads_local`` is already normalized (local layout, no leading shard
    dim). Returns ([1, ...]-restacked params, state)."""
    import optax

    p_local = jax.tree.map(lambda t: t[0], params)
    s_local = jax.tree.map(lambda t: t[0], opt_state)
    updates, s_local = optimizer.update(grads_local, s_local, p_local)
    p_local = optax.apply_updates(p_local, updates)
    return (
        jax.tree.map(lambda t: t[None], p_local),
        jax.tree.map(lambda t: t[None], s_local),
    )


def stacked_train_update(optimizer, params, opt_state, value_and_grad_fn,
                         data_axis: str):
    """One update on stacked shards, inside a vma-checked shard_map:
    strip the leading shard dim, differentiate, normalize the data-axis
    gradient sum, apply, restack.

    Under vma-checked shard_map the transpose ALREADY psums cotangents
    over every axis the parameter is invariant on (the data axis here) —
    an explicit pmean would double-count; dividing by the axis size turns
    that sum into the data-average.
    """
    p_local = jax.tree.map(lambda t: t[0], params)
    loss, grads = value_and_grad_fn(p_local)
    nd = _axis_size(data_axis)
    grads = jax.tree.map(lambda g: g / nd, grads)
    new_params, new_state = apply_stacked_update(
        optimizer, params, opt_state, grads
    )
    return new_params, new_state, loss
