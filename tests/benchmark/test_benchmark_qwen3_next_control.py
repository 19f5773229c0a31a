"""The control of `correct` for ``qwen3next-train-1chip``, at a size a test run
can hold: the plain reference computed one precision below the
configuration's (both operands of every product rounded to int8: the linear
layers, QK^T and PV, the experts, the delta rule's own products), put in the
program's place, comes out as not correct, while the program comes out as
correct. The committed limits are the chip's at full size (PERF.md section 6
gives the readings). At the cell's rehearsal sizes (hidden 64) per-tensor
int8 is no coarser than bfloat16 in any number a run compares (read over 8
seeds: program first_grad_norm 0.0022-0.0062, control 0.0060-0.0108), so
this test runs at hidden 256 with limits of its own, set the same way from
readings on the CPU over seeds 5, 6, 7: program at most loss_rel 3.1e-4,
first_grad_norm 0.0076, update_norm 0.0026; control at least 3.2e-4, 0.0106,
0.0032. Only first_grad_norm separates the two there (a top-k choice near a
tie flips in bfloat16 as in int8, and AdamW's first steps turn a flipped
token into a moved loss), and one limit that refuses is what the comparison
needs; the other two stand at twice the program's largest."""

import jax

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import check_train, manifest
from benchmark import run as bench_run
from benchmark.kinds import train_steps as kind

SIZE = {"hidden_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 64,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 64, "linear_value_head_dim": 64,
        "num_experts": 8, "num_experts_routed": 16, "num_experts_per_tok": 2,
        "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128,
        "vocab_size": 2048}
LIMITS = {"loss_rel": 6.2e-4, "first_grad_norm": 0.008, "update_norm": 0.0052}


def test_int8_reference_in_the_programs_place_is_not_correct():
    cell = manifest.Cell(manifest.load_manifest(), "qwen3next-train-1chip",
                         rehearse=True)
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
    loop, fresh = kind.build(ctx, batches)
    program = kind._program_numbers(cell, loop, fresh)

    ok, rows = check_train.verdict(check_train.compare(program, reference),
                                   LIMITS)
    assert ok, rows
    ok, rows = check_train.verdict(check_train.compare(control, reference),
                                   LIMITS)
    assert not ok, rows
