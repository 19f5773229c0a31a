"""The optimizers of the configurations, written out in ``jax.numpy`` as the
papers give them (and as optax computes them): AdamW with decoupled weight
decay."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def init(opt, params):
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    if opt["name"] == "adamw":
        return {"m": zeros(), "v": zeros(), "t": 0}
    raise ValueError(f"no reference for optimizer {opt['name']!r}")


def _adamw(opt, t, p, g, m, v):
    b1, b2 = opt["b1"], opt["b2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    m_hat = m / (1 - b1 ** t)
    v_hat = v / (1 - b2 ** t)
    u = m_hat / (jnp.sqrt(v_hat) + opt["eps"]) + opt["weight_decay"] * p
    return p - opt["learning_rate"] * u, m, v


@functools.lru_cache(maxsize=None)
def _adamw_step(opt_items):
    """One jitted program per optimizer setting, built once a process."""
    opt = dict(opt_items)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def step(p, g, m, v, t):
        out = jax.tree.map(
            lambda p, g, m, v: _adamw(opt, t, p, g, m, v), p, g, m, v
        )
        pick = lambda i: jax.tree.map(
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple)
        )
        return pick(0), pick(1), pick(2)

    return step


def update(opt, params, grads, state):
    """One step; returns ``(params, state)``. Arguments are consumed."""
    if opt["name"] != "adamw":
        raise ValueError(f"no reference for optimizer {opt['name']!r}")
    t = state["t"] + 1
    step = _adamw_step(tuple(sorted(opt.items())))
    p, m, v = step(params, grads, state["m"], state["v"], jnp.float32(t))
    return p, {"m": m, "v": v, "t": t}
