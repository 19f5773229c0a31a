"""What ``ops/pallas_attention._plan`` gives the benchmark's two
configurations: the GPT-2-medium cells' call is what it was before the second
model came (512 x 512 tiles, 4 rows a grid step), and the Qwen3-Next cell's
call (16 heads of width 256 at T 8192) fits the kernel's VMEM budget as it
stands, with fewer rows a step."""

import pytest

from horovod_tpu.ops import pallas_attention as pa


def test_plan_at_the_gpt2_medium_cells_shape_is_unchanged():
    # [8 sequences x 16 heads, 1024, 64] bf16 in and out
    assert pa._plan(128, 1024, 1024, 64, 2, 2, None, None) == (512, 512, 4)


def test_plan_at_head_width_256_and_8192_tokens_fits_vmem():
    bq, bk, rows = pa._plan(16, 8192, 8192, 256, 2, 2, None, None)
    assert (bq, bk, rows) == (512, 512, 2)
    assert pa._step_vmem_bytes(rows, bq, bk, 256, 2, 2) <= pa._VMEM_BUDGET
    # one more row of the folded batch x heads axis would not
    assert pa._step_vmem_bytes(4, bq, bk, 256, 2, 2) > pa._VMEM_BUDGET
    # 16 rows of q blocks x 16 of k blocks x 8 steps of rows, the causal
    # lower half (and the diagonal) computed
    assert pa._pairs_visited(8192, 8192, bq, bk, True) == pytest.approx(
        (16 * 17 / 2) / 256)


@pytest.mark.parametrize("d,in_size,expect", [
    (64, 4, (512, 512, 2)),      # f32 operands at the old width
    (128, 2, (512, 512, 4)),
    (256, 4, (512, 512, 1)),     # f32 operands at the new width
])
def test_plan_follows_width_and_operand_size(d, in_size, expect):
    assert pa._plan(64, 2048, 2048, d, in_size, in_size, None, None) == expect
