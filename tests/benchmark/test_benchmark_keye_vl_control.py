"""The controls of `correct` for ``keyevl-train-1chip``, at the cell's
rehearsal widths (top 16 of 128 positions: the selection binds) over EIGHT
rows a step: the plain reference computed one precision below the
configuration's (both operands of every bfloat16 product rounded to int8: the
projections, the indexer's scores, QK^T and PV, the experts; the router stays
float32), put in the program's place, comes out as not correct; so do the
program with every causal key selected (dense attention under this model's
name) and the program without ``L_I`` (the indexer's leaves take no
gradient); the program itself comes out as correct: on each of three seeds.
The committed limits are the chip's at full size (PERF.md section 6 gives the
readings). This test runs with limits of its own, set the same way from
readings on the CPU over the three seeds (READINGS below). At 16 keys a query
one flipped choice moves a small leaf's gradient as far as int8 rounding does,
and the worst leaf is one of the indexer's 8-wide vectors: over the
rehearsal's two rows a step the program read up to 0.045 and the control as
little as 0.026, so the test takes eight rows, where a leaf's gradient is the
sum over four times the queries."""

import jax
import jax.numpy as jnp
import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import check_train, manifest
from benchmark import run as bench_run
from benchmark.kinds import train_steps as kind

CELL = "keyevl-train-1chip"
ROWS = 8
# READINGS (loss_rel / first_grad_norm / update_norm over the three seeds):
# program at most 5.2e-5 / 0.0137 / 0.0148; int8 control 3.7e-5 to 8.3e-5 /
# 0.0306 to 0.0437 / 0.0092 to 0.0249; every causal key at least 6.0e-3 /
# 0.304 / 0.0501; no L_I 0.039 / 1.0 / 1.0. first_grad_norm stands between the
# program's largest and the control's smallest, 1.5 times from either; the
# loss and update_norm between the program's largest and the dense model's
# smallest (neither moves with the precision).
LIMITS = {"loss_rel": 1.0e-3, "first_grad_norm": 0.02, "update_norm": 0.035}
SEEDS = (2147500000, 2147604729, 2147709458)


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_reference_and_two_lesser_models_are_not_correct(monkeypatch,
                                                              seed):
    cell = manifest.Cell(manifest.load_manifest(), CELL, rehearse=True)
    cell.traffic["per_chip_batch"] = ROWS

    class Args:
        seconds, trace = 1.0, 0

    Args.seed = seed

    device = jax.devices()[0]
    ctx = bench_run.Context(cell, Args, [device])
    batches = cell.family.make_batches(
        cell.config, cell.traffic, cell.traffic["per_chip_batch"], Args.seed,
        cell.traffic["check_steps"])
    reference = kind.reference_numbers(cell, batches, Args.seed, device)
    control = kind.reference_numbers(
        cell, batches, Args.seed, device,
        precision=cell.config["train"]["control_precision"],
    )

    def program():
        loop, fresh = kind.build(ctx, batches)
        return kind._program_numbers(cell, loop, fresh)

    def over(numbers):
        ok, rows = check_train.verdict(
            check_train.compare(numbers, reference), LIMITS)
        return ok, [r["number"] for r in rows if not r["within"]]

    assert over(program()) == (True, [])
    assert over(control) == (False, ["first_grad_norm"])

    from horovod_tpu.models import keye_vl as km

    everything = ["loss_rel", "first_grad_norm", "update_norm"]
    # every causal key selected: dense attention under this model's name (the
    # indexer still scores and is still trained, toward another target)
    select = km.select_top_k
    with monkeypatch.context() as m:
        m.setattr(km, "select_top_k", lambda q_i, k_i, w, top_k: select(
            q_i, k_i, w, top_k=k_i.shape[1]))
        assert over(program()) == (False, everything)
    # no L_I: the indexer's leaves take no gradient and stay where they were
    monkeypatch.setattr(km, "index_kl",
                        lambda *a, **kw: jnp.zeros((), jnp.float32))
    assert over(program()) == (False, everything)
