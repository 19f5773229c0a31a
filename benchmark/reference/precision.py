"""The precisions a plain reference can be computed in.

``highest`` is the reference proper: float32 with full-precision products.
``int8`` is the control of "How correct is decided" for a configuration whose
products are bfloat16: the same arithmetic one step lower (both operands of
every product rounded to int8), which the comparison has to refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_int8(x):
    """Symmetric per-tensor int8, straight-through for the gradient."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def operand(x, precision):
    """One operand of a product, rounded as ``precision`` says."""
    if precision == "highest":
        return x
    if precision == "int8":
        return _fake_int8(x)
    raise ValueError(f"unknown precision {precision!r}")


def matmul(a, b, precision):
    return jnp.matmul(operand(a, precision), operand(b, precision),
                      precision=HIGHEST)
