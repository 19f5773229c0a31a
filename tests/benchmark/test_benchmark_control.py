"""The control of `correct`, at a size a test run can hold: the plain
reference computed one precision below the configuration's (both operands of
every product rounded to int8, for a bfloat16 configuration), put in the
program's place, has to come out as not correct, while the program itself
comes out as correct. The committed limits are the chip's at full size
(PERF.md section 6 gives the readings); this test has its own, set the same
way from readings at its size on the CPU over seeds 5, 6, 7: program at most
loss_rel 1.72e-5, first_grad_norm 0.00289, update_norm 0.00086; control at
least 9.5e-5, 0.139, 0.0063. Each limit is three times the program's
largest."""

import jax

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import check_train, manifest
from benchmark import run as bench_run
from benchmark.kinds import train_steps as kind

SIZE = {"n_embd": 256, "n_head": 4, "n_layer": 4, "n_positions": 256,
        "n_ctx": 256, "vocab_size": 2048}
LIMITS = {"loss_rel": 5.2e-5, "first_grad_norm": 0.009, "update_norm": 0.0026}


def test_int8_reference_in_the_programs_place_is_not_correct():
    cell = manifest.Cell(manifest.load_manifest(), "gpt2m-train-1chip",
                         rehearse=True)
    cell.config.update(SIZE)
    cell.traffic.update(seq_len=256, per_chip_batch=4)

    class Args:
        seed, seconds, trace = 6, 1.0, 0

    device = jax.devices()[0]
    ctx = bench_run.Context(cell, Args, [device])
    batches = cell.family.make_batches(cell.config, cell.traffic, 4,
                                       Args.seed, 3)
    reference = kind.reference_numbers(cell, batches, Args.seed, device)
    control = kind.reference_numbers(
        cell, batches, Args.seed, device,
        precision=cell.config["train"]["control_precision"],
    )
    loop, fresh = kind.build(ctx, batches)
    program = kind._program_numbers(cell, loop, fresh)

    ok, rows = check_train.verdict(check_train.compare(program, reference),
                                   LIMITS)
    assert ok, rows
    ok, rows = check_train.verdict(check_train.compare(control, reference),
                                   LIMITS)
    assert not ok, rows
