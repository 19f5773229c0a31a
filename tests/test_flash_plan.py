"""What ``ops/pallas_attention._plan`` gives the benchmark's three
configurations: the GPT-2-medium cells' call is what it was before the second
model came (512 x 512 tiles, 4 rows a grid step), the Qwen3-Next cell's
call (16 heads of width 256 at T 8192) fits the kernel's VMEM budget as it
stands, with fewer rows a step, and the LFM2 cell's (4 sequences x 32 heads of
width 64 at T 8192) is the GPT-2-medium plan over a longer sequence. The
backward kernels' plan (``_plan_bwd``) fits the same budget at all three
shapes by its own count."""

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


def test_plan_at_head_width_64_and_8192_tokens_is_the_gpt2_plan():
    # the LFM2 cell: [4 sequences x 32 heads, 8192, 64] bf16 in and out
    bq, bk, rows = pa._plan(128, 8192, 8192, 64, 2, 2, None, None)
    assert (bq, bk, rows) == (512, 512, 4)
    assert pa._step_vmem_bytes(rows, bq, bk, 64, 2, 2) <= pa._VMEM_BUDGET
    assert pa._step_vmem_bytes(8, bq, bk, 64, 2, 2) > pa._VMEM_BUDGET
    assert pa._pairs_visited(8192, 8192, bq, bk, True) == pytest.approx(
        (16 * 17 / 2) / 256)


@pytest.mark.parametrize("d,in_size,expect", [
    (64, 4, (512, 512, 2)),      # f32 operands at the old width
    (128, 2, (512, 512, 4)),
    (256, 4, (512, 512, 1)),     # f32 operands at the new width
])
def test_plan_follows_width_and_operand_size(d, in_size, expect):
    assert pa._plan(64, 2048, 2048, d, in_size, in_size, None, None) == expect


@pytest.mark.parametrize("bh,t,d,expect", [
    # the GPT-2-medium cells: a row's whole dq (0.5 MB f32) fits beside
    # 512 x 512 tiles, so one kernel does 5 products a pair
    (128, 1024, 64, (512, 512, 1, True)),
    # the Qwen3-Next cell: a row's dq is 8 MB, so dK/dV and dQ kernels
    (16, 8192, 256, (512, 512, 1, False)),
    # d 64 at T 4096: a row's dq no longer fits; two kernels, 2 rows a step
    (16, 4096, 64, (512, 512, 2, False)),
    # the LFM2 cell: a row's dq is 2 MB and does not fit either
    (128, 8192, 64, (512, 512, 2, False)),
])
def test_backward_plan_at_the_cells_shapes_fits_vmem(bh, t, d, expect):
    """Beside q, k, v the backward holds do, two accumulators and six
    [Bq, Bk] f32 temporaries: fewer rows of bh a step than the forward;
    the form follows from whether some rows' whole dq fits as well."""
    bq, bk, rows, one_pass = pa._plan_bwd(bh, t, t, d, 2, None, None)
    assert (bq, bk, rows, one_pass) == expect
    whole = t if one_pass else 0
    count = lambda r: pa._bwd_step_vmem_bytes(r, bq, bk, d, 2, whole)
    assert count(rows) <= pa._VMEM_BUDGET
    nxt = next(r for r in range(rows + 1, bh + 1) if bh % r == 0)
    assert count(nxt) > pa._VMEM_BUDGET
    assert pa._bwd_step_vmem_bytes(1, bq, bk, d, 2, t) > pa._VMEM_BUDGET or (
        one_pass)
    assert rows <= pa._plan(bh, t, t, d, 2, 2, None, None)[2]


def test_backward_plan_keeps_the_callers_blocks_and_halves_its_own():
    assert pa._plan_bwd(4, 32, 64, 16, 4, 8, 16) == (8, 16, 4, True)
    # f32 operands at width 256: a 512 x 512 step of one row overruns
    assert pa._bwd_step_vmem_bytes(1, 512, 512, 256, 4) > pa._VMEM_BUDGET
    assert pa._plan_bwd(16, 2048, 2048, 256, 4, None, None)[:2] == (256, 256)


def test_plans_at_keys_of_192_and_values_of_128():
    """The Xing4.0 cell: [32 heads, 8192, 192 | 128] bf16. In VMEM a 192-wide
    block takes 256 lanes and a 128-wide one 128: two rows a forward step
    where keys AND values of 192 would leave one, and one row a backward
    step, in two kernels (a row's dq is 8192 x 256 lanes of f32, 8 MB)."""
    bq, bk, rows = pa._plan(32, 8192, 8192, 192, 2, 2, None, None, 128)
    assert (bq, bk, rows) == (512, 512, 2)
    count = lambda r, d_v: pa._step_vmem_bytes(r, bq, bk, 192, 2, 2, d_v)
    assert count(2, 128) <= pa._VMEM_BUDGET < count(4, 128)
    assert pa._plan(32, 8192, 8192, 192, 2, 2, None, None)[2] == 2
    assert count(2, 128) < count(2, None) == count(2, 192)
    assert pa._plan_bwd(32, 8192, 8192, 192, 2, None, None, 128) == (
        512, 512, 1, False)
    held = pa._bwd_step_vmem_bytes(1, 512, 512, 192, 2, 0, 128)
    assert held <= pa._VMEM_BUDGET
    assert held < pa._bwd_step_vmem_bytes(1, 512, 512, 192, 2)
    assert pa._bwd_step_vmem_bytes(2, 512, 512, 192, 2, 0, 128) \
        > pa._VMEM_BUDGET
    assert pa._pairs_visited(8192, 8192, bq, bk, True) == pytest.approx(
        (16 * 17 / 2) / 256)


@pytest.mark.parametrize("bh,t,d,in_size", [
    (128, 1024, 64, 2), (16, 8192, 256, 2), (128, 8192, 64, 2),
    (64, 2048, 128, 2), (64, 2048, 256, 4), (8, 1024, 64, 4),
])
def test_equal_widths_plan_as_one_width_did(bh, t, d, in_size):
    """``d_v`` left out, or given as the keys' width, counts and plans what
    the one-width kernels counted and planned (the pins above are those)."""
    one = pa._plan(bh, t, t, d, in_size, in_size, None, None)
    assert pa._plan(bh, t, t, d, in_size, in_size, None, None, d) == one
    bq, bk, rows = one
    dl = -(-d // 128) * 128
    assert pa._step_vmem_bytes(rows, bq, bk, d, in_size, in_size, d) == (
        2 * rows * dl * ((bq + 2 * bk) * in_size + bq * in_size)
        + rows * bq * (dl + 2 * 128) * 4 + 4 * bq * bk * 4)
    back = pa._plan_bwd(bh, t, t, d, in_size, None, None)
    assert pa._plan_bwd(bh, t, t, d, in_size, None, None, d) == back
    for whole in (0, t):
        assert pa._bwd_step_vmem_bytes(1, bq, bk, d, in_size, whole, d) == (
            pa._bwd_step_vmem_bytes(1, bq, bk, d, in_size, whole))
