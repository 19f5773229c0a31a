"""``check_train.reference_steps`` holds ONE gradient tree beside the
reference's parameters and moments where it held three (PR 40), and computes
what it computed: against the function as it stood, kept here as the oracle,
the losses, the first gradient's norms and the update's norms are equal to the
bit; between two steps the device holds three parameter trees, not five."""

import gc

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import check_train, manifest
from benchmark.reference import optim
from benchmark.weights import make_params

SEED, STEPS = 11, 3


def old_reference_steps(ref_loss, fresh_params, batches, rows, opt,
                        precision):
    """The function as PR 22 wrote it and PRs 25 and 32 sized their
    references round: ``g`` and ``acc``, then ``grads``, outlive the step."""
    grad_block = jax.jit(jax.value_and_grad(
        lambda p, b: ref_loss(p, b, precision)
    ))
    params = fresh_params()
    state = optim.init(opt, params)
    losses, first_grad = [], None
    for batch in batches:
        blocks = check_train._blocks(batch, rows)
        total, acc = 0.0, None
        for block in blocks:
            value, g = grad_block(params, block)
            total += float(value)
            acc = g if acc is None else check_train._accumulate(acc, g)
        grads = jax.tree.map(lambda x: x / len(blocks), acc)
        losses.append(total / len(blocks))
        if first_grad is None:
            first_grad = np.asarray(check_train.leaf_norms(grads))
        params, state = optim.update(opt, params, grads, state)
    update = np.asarray(check_train.diff_norms(params, fresh_params()))
    return {"losses": losses, "first_grad_norms": first_grad,
            "update_norms": update}


def _case(blocks_a_step):
    """The first cell's reference at its rehearsal size; its blocks are one
    row, so the batch's rows are the blocks of a step."""
    cell = manifest.Cell(manifest.load_manifest(), "gpt2m-train-1chip",
                         rehearse=True)
    cfg, ref = cell.config, cell.reference()
    rows = ref.block_rows(cfg, blocks_a_step)
    assert rows == 1
    batches = cell.family.make_batches(cfg, cell.traffic, blocks_a_step,
                                       SEED, STEPS)
    spec = cell.family.param_spec(cfg)
    one = SingleDeviceSharding(jax.devices()[0])
    return dict(
        ref_loss=lambda p, b, pr: ref.loss(p, b, cfg, pr),
        fresh_params=lambda: make_params(spec, SEED, one),
        batches=batches, rows=rows, opt=cfg["train"]["optimizer"],
        precision="highest",
    )


# one block (the hybrid cells: no division), two (a power of two) and three
# (a divisor whose reciprocal is not exact: a product with it would show)
@pytest.mark.parametrize("blocks_a_step", [1, 2, 3])
def test_numbers_are_bit_equal_to_the_old_functions(blocks_a_step):
    case = _case(blocks_a_step)
    want = old_reference_steps(**case)
    got = check_train.reference_steps(**case)
    assert len(got["losses"]) == STEPS
    assert got["losses"] == want["losses"]
    for key in ("first_grad_norms", "update_norms"):
        assert got[key].dtype == want[key].dtype
        assert np.array_equal(got[key], want[key]), key
    assert np.all(want["update_norms"] > 0)   # the steps did move the leaves


def _live_bytes():
    return sum(x.nbytes for x in jax.live_arrays())


@pytest.mark.parametrize("blocks_a_step", [1, 2])
def test_between_two_steps_three_trees_are_alive_not_five(blocks_a_step):
    case = _case(blocks_a_step)
    tree = sum(x.nbytes for x in jax.tree.leaves(case["fresh_params"]()))
    gc.collect()
    base = _live_bytes()

    def sampled(fn):
        seen = []

        def feed():
            for batch in case["batches"]:
                seen.append(_live_bytes() - base)
                yield batch

        fn(**dict(case, batches=feed()))
        gc.collect()
        assert _live_bytes() - base < 0.1 * tree   # everything is freed
        return seen

    # before the first step: parameters and two moments, either way
    old, new = sampled(old_reference_steps), sampled(check_train.reference_steps)
    assert 3 * tree <= old[0] < 3.1 * tree and 3 * tree <= new[0] < 3.1 * tree
    # between steps the old function also holds the sum (with one block the
    # block's own gradient; with more, that beside it) and the mean
    for seen in old[1:]:
        assert seen >= (5 if blocks_a_step == 1 else 6) * tree
    for seen in new[1:]:
        assert 3 * tree <= seen < 3.1 * tree
