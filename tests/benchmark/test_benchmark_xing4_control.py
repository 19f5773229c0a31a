"""The controls of `correct` for ``xing4-train-1chip``, at the cell's rehearsal
size: the plain reference computed one precision below the configuration's
(both operands of every bfloat16 product rounded to int8: the projections,
QK^T and PV, the experts, the streams' weighted sums; the router and the
mixing maps stay float32, as the configuration states them), put in the
program's place, comes out as not correct; so do the program with the stream
mixes replaced by a plain residual and the program whose scores leave out the
rotary part of the keys (``q_rope k_rope^T`` dropped); the program itself
comes out as correct. The committed limits are the chip's at full size
(PERF.md section 6 gives the readings). This test runs with limits of its
own, set the same way from readings on the CPU over seeds 2147500000,
2147604729 and 2147709458: program at most loss_rel 2.5e-5, first_grad_norm
0.0105, update_norm 0.305; int8 control at least 1e-5, 0.0346, 0.012; no
mixes 1.6e-4, 1.0, 1.0; no rotary keys 1e-5, 0.198, 0.244. first_grad_norm
stands between the program's largest and the smallest of the three controls.
update_norm reads a quarter on EVERY sound run, and that is no rounding of the
whole step: layer 0's attention mix sees four identical streams, so 20 of its
phi's 24 columns take a gradient that is rounding alone (tests/test_xing4.py
holds that), and Adam's first steps divide a gradient by its own size: in
bfloat16 those columns move by a whole step, in float32 (the reference, and
the int8 control, whose sums are float32) hardly at all. Its limit stands
between that reading and 1, which is what a state left unchanged reads and
what the mix-less program reads; the loss hardly moves with either precision
and has the rehearsal's limit."""

import jax
import jax.numpy as jnp

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import check_train, manifest
from benchmark import run as bench_run
from benchmark.kinds import train_steps as kind

CELL = "xing4-train-1chip"
LIMITS = {"loss_rel": 1.0e-3, "first_grad_norm": 0.02, "update_norm": 0.6}


def test_int8_reference_and_two_lesser_models_are_not_correct(monkeypatch):
    cell = manifest.Cell(manifest.load_manifest(), CELL, rehearse=True)

    class Args:
        seed, seconds, trace = 2147604729, 1.0, 0

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

    from horovod_tpu.models import xing4 as xm

    # the scores without q_rope k_rope^T: both rotary parts zero (the leaves
    # stay in the tree): another model, and the comparison says so
    with monkeypatch.context() as m:
        m.setattr(xm, "rotary", lambda x, positions, **kw: jnp.zeros(
            x.shape, jnp.float32))
        assert over(program()) == (False, ["first_grad_norm"])
    # a plain residual in place of the stream mixes: x' = x + F(x) on every
    # stream alike, the mixes' leaves unread
    monkeypatch.setattr(xm.StreamMix, "pre", lambda self, s: (s[0], None))
    monkeypatch.setattr(xm.StreamMix, "post",
                        lambda self, s, y, maps: s + y[None])
    assert over(program()) == (False, ["first_grad_norm", "update_norm"])
