"""The controls of `correct` for ``lfm2moe-train-1chip``, at a size a test run
can hold: the plain reference computed one precision below the
configuration's (both operands of every product rounded to int8: the linear
layers, the gates and the taps, QK^T and PV, the experts), put in the
program's place, comes out as not correct; so does the program with the
selection bias left out of the choice; the program itself comes out as
correct. The committed limits are the chip's at full size (PERF.md section 6
gives the readings). This test runs at hidden 256 with limits of its own, set
the same way from readings on the CPU over seeds 5, 6, 7, 8 at the
configuration's learning rate of 1e-5: program at most loss_rel 9.86e-5,
first_grad_norm 0.0140, update_norm 0.00290; int8 control at least 1.50e-4,
0.0316, 0.00315; the bias-less program (seeds 5, 6) first_grad_norm 0.065.
first_grad_norm stands between the program's largest and the control's
smallest; loss_rel and update_norm hardly tell the two apart (a top-k choice
near a tie flips in bfloat16 as in int8, and at this learning rate three
steps move a norm's weight of one by some 250 float32 steps, so rounding is
a quarter of a percent of the change on either side) and stand at three
times the program's largest."""

import jax

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import check_train, manifest
from benchmark import run as bench_run
from benchmark.kinds import train_steps as kind

CELL = "lfm2moe-train-1chip"
SIZE = {"hidden_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 512,
        "moe_intermediate_size": 128, "num_experts": 8,
        "num_experts_routed": 16, "num_experts_per_tok": 2,
        "vocab_size": 2048}
LIMITS = {"loss_rel": 3.0e-4, "first_grad_norm": 0.021, "update_norm": 0.0087}


def test_int8_reference_and_a_biasless_program_are_not_correct(monkeypatch):
    cell = manifest.Cell(manifest.load_manifest(), CELL, rehearse=True)
    cell.config.update(SIZE)
    cell.traffic.update(seq_len=256, per_chip_batch=2)

    class Args:
        seed, seconds, trace = 6, 1.0, 0

    device = jax.devices()[0]
    ctx = bench_run.Context(cell, Args, [device])
    batches = cell.family.make_batches(cell.config, cell.traffic, 2,
                                       Args.seed, 3)
    reference = kind.reference_numbers(cell, batches, Args.seed, device)
    control = kind.reference_numbers(
        cell, batches, Args.seed, device,
        precision=cell.config["train"]["control_precision"],
    )

    def program():
        loop, fresh = kind.build(ctx, batches)
        return kind._program_numbers(cell, loop, fresh)

    verdict = lambda numbers: check_train.verdict(
        check_train.compare(numbers, reference), LIMITS)
    ok, rows = verdict(program())
    assert ok, rows
    ok, rows = verdict(control)
    assert not ok, rows
    assert [r["number"] for r in rows if not r["within"]] == [
        "first_grad_norm"]

    # the same program with the bias left out of the choice (the leaf stays
    # in the tree, unread): another model, and the comparison says so
    from horovod_tpu.models import lfm2_moe as lm
    from horovod_tpu.parallel import ep

    monkeypatch.setattr(
        lm, "dropless_moe",
        lambda *a, select_bias=None, **kw: ep.dropless_moe(*a, **kw))
    ok, rows = verdict(program())
    assert not ok, rows
    assert not next(r for r in rows
                    if r["number"] == "first_grad_norm")["within"]
