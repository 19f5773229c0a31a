"""What ``ops/pallas_attention._plan`` gives the benchmark's three
configurations: the GPT-2-medium cells' call is what it was before the second
model came (512 x 512 tiles, 4 rows a grid step), the Qwen3-Next cell's
call (16 heads of width 256 at T 8192) fits the kernel's VMEM budget as it
stands, with fewer rows a step, and the LFM2 cell's (4 sequences x 32 heads of
width 64 at T 8192) is the GPT-2-medium plan over a longer sequence. The
backward's plan (``_plan_bwd``) takes its tiles and rows under the same
budget by its own count, and its FORM from whether some of those rows' whole
``dq`` fits beside the tiles: under that budget at GPT-2-medium's T 1024 (no
limit asked for: the call is what it was), under the larger ``_WHOLE_DQ_BUDGET``
and a raised scoped limit at the four long cells' shapes (the selection's
tile counted), and not at all at T 32768 x d 256 or at float32 operands of
T 16384 x d 256, where the dK/dV and the dQ kernel run as before."""

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


def test_plan_at_head_width_128_and_8192_tokens_over_64_rows():
    # the Nemotron-H cell: [2 sequences x 32 heads, 8192, 128] bf16 in and
    # out: the GPT-2 tiles, four rows a step, 4096 grid steps; one pass of
    # the backward with two rows' whole dq (test below)
    bq, bk, rows = pa._plan(64, 8192, 8192, 128, 2, 2, None, None)
    assert (bq, bk, rows) == (512, 512, 4)
    assert pa._step_vmem_bytes(rows, bq, bk, 128, 2, 2) <= pa._VMEM_BUDGET
    assert pa._step_vmem_bytes(8, bq, bk, 128, 2, 2) > pa._VMEM_BUDGET
    assert pa._plan_bwd(64, 8192, 8192, 128, 2, None, None) == (
        512, 512, 2, True, 28442624)


@pytest.mark.parametrize("d,in_size,expect", [
    (64, 4, (512, 512, 2)),      # f32 operands at the old width
    (128, 2, (512, 512, 4)),
    (256, 4, (512, 512, 1)),     # f32 operands at the new width
])
def test_plan_follows_width_and_operand_size(d, in_size, expect):
    assert pa._plan(64, 2048, 2048, d, in_size, in_size, None, None) == expect


_MB = 2 ** 20


def _count(d, in_size, d_v=None, sel_heads=None, bq=512, bk=512):
    """``count(rows, whole_t_q)`` of a step at these widths and tiles, as
    ``_plan_bwd`` counts it (a selection's tile included)."""
    tile = pa._sel_tile_bytes(bq, bk) if sel_heads else 0
    return lambda rows, whole=0: tile + pa._bwd_step_vmem_bytes(
        rows, bq, bk, d, in_size, whole, d_v)


@pytest.mark.parametrize("bh,t,d,expect", [
    # the GPT-2-medium cells, and what the TP forward passes with few local
    # heads: a row's whole dq (0.5 MB f32) fits beside 512 x 512 tiles under
    # the budget the call has without asking, so one kernel does 5 products
    # a pair. Plan and bytes are the parent's, and no limit is passed.
    (128, 1024, 64, (512, 512, 1, True, 10551296)),
    (8, 1024, 64, (512, 512, 1, True, 10551296)),
    (96, 1024, 64, (512, 512, 1, True, 10551296)),
])
def test_backward_plan_at_t_1024_is_the_parents(bh, t, d, expect):
    plan = pa._plan_bwd(bh, t, t, d, 2, None, None)
    assert plan == expect
    assert plan[4] <= pa._VMEM_BUDGET and pa._vmem_limit(plan[4]) is None
    count = _count(d, 2)
    assert plan[4] == count(1, t)
    # two rows' dq would fit the larger budget: the first budget that holds
    # some rows decides, so a call that fitted keeps its plan
    assert pa._VMEM_BUDGET < count(2, t) <= pa._WHOLE_DQ_BUDGET
    assert plan[2] <= pa._plan(bh, t, t, d, 2, 2, None, None)[2]


@pytest.mark.parametrize("bh,t,d,d_v,sel_heads,rows,mb", [
    # the LFM2 cell: a row's dq is 2 MB in f32 and 2 x 1 MB of output block
    (128, 8192, 64, None, None, 2, 27.12),
    # the Qwen3-Next cell: 8 MB and 2 x 4 MB a row
    (16, 8192, 256, None, None, 1, 27.06),
    # the Xing4.0 cell: keys of 192 take 256 lanes, values of 128 their own
    (32, 8192, 192, 128, None, 1, 26.06),
    # the Keye-VL cell: heads of ONE batch row, the selection's tile counted
    (32, 16384, 128, None, 32, 2, 44.62),
    # the Nemotron-H cell: 2 sequences x 32 heads of 128 (2 key/value heads
    # repeated 16 times): a row's dq is 4 MB and 2 x 2 MB of output block
    (64, 8192, 128, None, None, 2, 27.12),
    # d 64 at T 4096, and what the selection's shape plans without one
    (16, 4096, 64, None, None, 2, 19.12),
    (32, 16384, 128, None, None, 2, 43.12),
])
def test_backward_plan_at_the_long_cells_shapes_is_one_pass(
        bh, t, d, d_v, sel_heads, rows, mb):
    """The two kernels' tiles and rows, chosen under ``_VMEM_BUDGET`` as
    before, and of those rows as many as have their whole dq fit
    ``_WHOLE_DQ_BUDGET``; the call asks for ``_WHOLE_DQ_VMEM``."""
    plan = pa._plan_bwd(bh, t, t, d, 2, None, None, d_v, sel_heads=sel_heads)
    assert plan[:4] == (512, 512, rows, True)
    count = _count(d, 2, d_v, sel_heads)
    assert plan[4] == count(rows, t) and round(plan[4] / _MB, 2) == mb
    assert pa._VMEM_BUDGET < count(1, t) <= plan[4] <= pa._WHOLE_DQ_BUDGET
    assert pa._vmem_limit(plan[4]) == pa._WHOLE_DQ_VMEM > pa._WHOLE_DQ_BUDGET
    # the rows are the two-kernel plan's at most (its step fits the small
    # budget; one more row of it, or of the whole dq, fits neither)
    assert count(rows) <= pa._VMEM_BUDGET < count(2 * rows)
    assert count(2 * rows, t) > pa._VMEM_BUDGET
    assert rows <= pa._plan(bh, t, t, d, 2, 2, None, None, d_v)[2]


@pytest.mark.parametrize("bh,t,d,in_size,d_v,sel_heads,expect", [
    # a row's dq is 32 MB in f32 alone
    (16, 32768, 256, 2, None, None, (512, 512, 1, False, 10551296)),
    (16, 32768, 192, 2, 128, None, (512, 512, 1, False, 9502720)),
    # float32 operands: 12 bytes an element of dq; T 16384 x 256 is 48 MB
    (16, 16384, 256, 4, None, None, (256, 256, 2, False, 8978432)),
    (16, 32768, 64, 4, None, None, (512, 512, 1, False, 10027008)),
    # under a selection at T 65536: 32 MB a head
    (32, 65536, 128, 2, None, 32, (512, 512, 2, False, 12189696)),
])
def test_backward_plan_where_no_whole_dq_fits_is_two_kernels(
        bh, t, d, in_size, d_v, sel_heads, expect):
    """The form no benchmark cell runs any longer keeps its guard: where one
    row's whole dq overruns ``_WHOLE_DQ_BUDGET`` the plan is the parent's two
    kernels, their step under ``_VMEM_BUDGET`` and no limit asked for."""
    plan = pa._plan_bwd(bh, t, t, d, in_size, None, None, d_v,
                        sel_heads=sel_heads)
    assert plan == expect
    bq, bk, rows = plan[:3]
    count = _count(d, in_size, d_v, sel_heads, bq, bk)
    assert plan[4] == count(rows) <= pa._VMEM_BUDGET
    assert count(1, t) > pa._WHOLE_DQ_BUDGET
    assert pa._vmem_limit(plan[4]) is None


def test_float32_operands_at_t_8192_fit_the_larger_budget():
    """12 bytes an element of dq: 12 MB a row at d 64, 24 MB at d 256 (whose
    tiles are 256 x 256): both fit, so both run one pass."""
    assert pa._plan_bwd(128, 8192, 8192, 64, 4, None, None) == (
        512, 512, 1, True, 23658496)
    assert pa._plan_bwd(16, 8192, 8192, 256, 4, None, None) == (
        256, 256, 1, True, 30703616)
    # T 1024 in float32 was two kernels: a row's whole dq is 0.06 MB over
    assert pa._plan_bwd(8, 1024, 1024, 64, 4, None, None) == (
        512, 512, 1, True, 12648448)
    assert pa._VMEM_BUDGET < 12648448


def test_backward_plan_keeps_the_callers_blocks_and_halves_its_own():
    assert pa._plan_bwd(4, 32, 64, 16, 4, 8, 16) == (8, 16, 4, True, 597504)
    # f32 operands at width 256: a 512 x 512 step of one row overruns
    assert pa._bwd_step_vmem_bytes(1, 512, 512, 256, 4) > pa._VMEM_BUDGET
    assert pa._plan_bwd(16, 2048, 2048, 256, 4, None, None)[:2] == (256, 256)


def test_plans_at_keys_of_192_and_values_of_128():
    """The Xing4.0 cell: [32 heads, 8192, 192 | 128] bf16. In VMEM a 192-wide
    block takes 256 lanes and a 128-wide one 128: two rows a forward step
    where keys AND values of 192 would leave one, and one row a backward
    step, in one pass (a row's dq is 8192 x 256 lanes of f32, 8 MB, and as
    much again in the output block's two buffers)."""
    bq, bk, rows = pa._plan(32, 8192, 8192, 192, 2, 2, None, None, 128)
    assert (bq, bk, rows) == (512, 512, 2)
    count = lambda r, d_v: pa._step_vmem_bytes(r, bq, bk, 192, 2, 2, d_v)
    assert count(2, 128) <= pa._VMEM_BUDGET < count(4, 128)
    assert pa._plan(32, 8192, 8192, 192, 2, 2, None, None)[2] == 2
    assert count(2, 128) < count(2, None) == count(2, 192)
    assert pa._plan_bwd(32, 8192, 8192, 192, 2, None, None, 128) == (
        512, 512, 1, True, 27328512)
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
