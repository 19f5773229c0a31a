"""Seeded weights, made on the device in one jitted call.

A family describes its parameter tree as nested dicts whose leaves are
``Leaf(shape, kind, std)``; :func:`make_params` fills the whole tree from the
seed in one program, already placed (replicated over ``sharding`` if given).
The same spec and seed give the same weights to the program and to the
reference, so neither takes anything the other has made.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    shape: Tuple[int, ...]
    kind: str  # "normal" | "zeros" | "ones"
    std: float = 0.0


def is_leaf(x):
    return isinstance(x, Leaf)


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def count(spec) -> int:
    total = 0
    for leaf in jax.tree.leaves(spec, is_leaf=is_leaf):
        n = 1
        for s in leaf.shape:
            n *= s
        total += n
    return total


def make_params(spec, seed: int, sharding=None):
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_leaf)

    def build(key):
        out = []
        for i, leaf in enumerate(leaves):
            if leaf.kind == "normal":
                x = leaf.std * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, jnp.float32
                )
            elif leaf.kind == "zeros":
                x = jnp.zeros(leaf.shape, jnp.float32)
            elif leaf.kind == "ones":
                x = jnp.ones(leaf.shape, jnp.float32)
            else:
                raise ValueError(f"unknown leaf kind {leaf.kind!r}")
            out.append(x)
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))
