"""`correct` for training: the program's first steps against the plain
reference's, number by number.

The reference follows the same batches from the same seeded weights, in blocks
of rows, on one chip (so a step that spans chips is compared with arithmetic
that has no wire in it). Numbers compared, each with its own limit (the cell's
file holds the limits; PERF.md holds the readings they were set from):

- ``loss_rel``: each step's loss, relative gap, the worst step;
- ``first_grad_norm``: per leaf, the norm of the first gradient as the
  optimizer got it (read back from its state after step one);
- ``update_norm``: per leaf, the norm of the parameters' change after the steps.

The per-leaf numbers are the gap between the two norms (not the norm of a
difference), against the reference's norm of that leaf or of its median leaf,
whichever is larger; the worst leaf is what is compared.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference import optim


@jax.jit
def leaf_norms(tree):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree.leaves(tree)
    ])


@jax.jit
def diff_norms(a, b):
    return leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _divide(acc, n):
    # n is an argument, not a constant of the program: a constant divisor XLA
    # may turn into a product with its reciprocal, which rounds otherwise
    return jax.tree.map(lambda x: x / n, acc)


def _blocks(batch, rows):
    n = len(batch[0])
    return [tuple(x[i:i + rows] for x in batch) for i in range(0, n, rows)]


def reference_steps(ref_loss, fresh_params, batches, rows, opt, precision):
    """Drive the reference through ``batches`` (one optimizer step each).
    Returns host numbers only; everything on the device is freed.

    Beside the parameters and the optimizer's moments ONE gradient tree is
    alive when a block's gradient is computed (a second, the sum so far, from
    a step's second block on): the sum is donated to its successor and to the
    mean, and no name holds a gradient past the update. Each hand-over waits
    for the device, because memory for a program's results is taken when it
    is enqueued, and what the program before it gives up is free only once
    that one has run. The reference then costs what the program costs, 16
    bytes a parameter, and a cell's size is not held down by its check."""
    grad_block = jax.jit(jax.value_and_grad(
        lambda p, b: ref_loss(p, b, precision)
    ))
    params = fresh_params()
    state = optim.init(opt, params)
    losses, first_grad = [], None
    for batch in batches:
        blocks = _blocks(batch, rows)
        total, grads = 0.0, None
        for block in blocks:
            value, g = grad_block(params, block)
            total += float(value)
            if grads is None:
                grads = g
            else:
                grads = jax.block_until_ready(_accumulate(grads, g))
            del g
        if len(blocks) > 1:   # x / 1 is x
            grads = _divide(grads, jnp.float32(len(blocks)))
        losses.append(total / len(blocks))
        if first_grad is None:
            first_grad = np.asarray(leaf_norms(grads))
        params, state = optim.update(opt, params, grads, state)
        del grads
        jax.block_until_ready(params)
    del state
    update = np.asarray(diff_norms(params, fresh_params()))
    return {"losses": losses, "first_grad_norms": first_grad,
            "update_norms": update}


def worst_leaf_gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = float(np.median(want))
    return float(np.max(np.abs(got - want) / np.maximum(want, floor)))


def leaf_gaps(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(want, float(np.median(want)))


def candidates(program, reference):
    """Other statistics of the same per-leaf norms, for ``calibrate.py``: when
    the worst leaf does not separate the control from sound runs, one of
    these may (PERF.md says which a cell compares and why)."""
    out = {}
    for key in ("first_grad_norms", "update_norms"):
        gaps = leaf_gaps(program[key], reference[key])
        total = lambda v: float(np.sqrt(np.sum(np.square(
            np.asarray(v, np.float64)))))
        out[key] = {
            "worst": float(gaps.max()), "worst_leaf": int(gaps.argmax()),
            "p90": float(np.quantile(gaps, 0.9)),
            "median": float(np.median(gaps)),
            "mean": float(np.mean(gaps)),
            "global": abs(total(program[key]) - total(reference[key]))
            / total(reference[key]),
        }
    return out


def compare(program, reference):
    """The numbers compared, by name."""
    return {
        "loss_rel": max(
            abs(a - b) / abs(b)
            for a, b in zip(program["losses"], reference["losses"])
        ),
        "first_grad_norm": worst_leaf_gap(program["first_grad_norms"],
                                          reference["first_grad_norms"]),
        "update_norm": worst_leaf_gap(program["update_norms"],
                                      reference["update_norms"]),
    }


def verdict(numbers, limits):
    """``(correct, lines)``: each number that the cell's file gives a limit,
    beside that limit. A cell compares the numbers its limits name."""
    rows = []
    ok = True
    for name, limit in limits.items():
        value = numbers[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        rows.append({"number": name, "value": value, "limit": limit,
                     "within": good})
    return ok, rows
