"""Per-token gather-sum of rows: ``out[t] = sum_c w[t, c] * rows[pos[t, c]]``
over the slots ``c`` whose ``pos[t, c] >= 0``, in slot order, float32.

What the dropless expert layer (``parallel/ep.dropless_moe``) sums with: a
token's held (token, expert) pairs are rows of a tile sorted by EXPERT, and
the sum is over TOKENS, so a scatter-add of the rows meets indices neither
sorted nor unique and applies them one after another, rows past the load
included. Here every output row is written once: no read-modify-write, no
order among rows, and only the rows some slot names are read, so whatever a
grouped product left in the rows past its last group never reaches the
result.

The Pallas kernel (``moe_combine`` in a trace) walks blocks of tokens. The
rows stay in HBM; a block's indices come in by block into scalar memory, one
row-sized asynchronous copy is started per named row, a slot's copies all in
flight while the slot before is summed, and the block's ``[tokens, D]``
float32 sum is written once. Which tokens of a block name a row in a slot, and
how many, is worked out by XLA beside the call (a compare and a sum), so the
kernel's loops run as many times as rows are named: a layer whose tokens hold
few of their ``k`` choices pays for the slots in use.
:func:`plan` takes the shapes: rows of whole lanes in float32 and a token
count that blocks divide; other shapes take the same sum as one XLA gather a
slot (``_xla``: no scatter either). A row that is whole lanes and not whole
``(8, 128)`` tiles (3584 is 28 sublanes) is laid out on a pitch of whole
tiles, so that every copy starts and ends on a tile's edge; a row of whole
tiles is its own pitch and is handed over as it is.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace as _trace
from . import pallas_attention as _pa
from .pallas_attention import _LANES, _VMEM_BUDGET

_SUBLANES = 8
_PREF_TOKENS = 256           # tokens a grid step, at most


def _pitch(width: int) -> int:
    """Sublanes from one row to the next in the layout the kernel copies
    from: the row's ``width / 128`` sublanes, in whole tiles of eight."""
    return -(-(width // _LANES) // _SUBLANES) * _SUBLANES


def plan(tokens: int, width: int, dtype) -> Optional[int]:
    """Tokens a grid step, or ``None`` where the kernel does not run: rows
    that are not float32, a width that is not whole lanes (a row is copied as
    ``width / 128`` sublanes of 128 lanes, on a pitch of whole tiles), a
    token count no block of whole sublanes divides. Two row buffers at the
    pitch and the pipeline's two output blocks are float32: ``16 * block *
    width`` bytes where a row is whole tiles."""
    if _refusal(tokens, width, dtype):
        return None
    row_bytes = 8 * (width + _pitch(width) * _LANES)
    block = _PREF_TOKENS
    while block > _SUBLANES and (tokens % block
                                 or block * row_bytes > _VMEM_BUDGET):
        block //= 2
    return block if tokens % block == 0 else None


def _refusal(tokens: int, width: int, dtype) -> Optional[str]:
    """Why the shapes are not the kernel's (the word the build ledger's
    fallback record carries), or None where they are."""
    if jnp.dtype(dtype) != jnp.float32:
        return "rows_not_float32"
    if width % _LANES:
        return "row_not_whole_lanes"
    if tokens % _SUBLANES:
        return "tokens_not_whole_blocks"
    return None


def _kernel(counts_ref, pos_s, tok_s, pos_v, w_v, rows_hbm, out_ref, buf, sem,
            *, block: int, slots: int, chunks: int, pitch: int):
    """One block of tokens. ``rows_hbm`` is the rows as ``[R * pitch,
    128]``: a row is ``chunks`` sublanes at the head of ``pitch`` whole
    ones, contiguous in HBM and in the buffer alike, so one copy moves it;
    the sum reads the buffer back eight tokens a time with a sublane stride
    of ``pitch``, which is one lane chunk of eight rows as the output block
    holds them."""
    i = pl.program_id(0)

    def row_copy(c, p, t):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(p * pitch, pitch), pitch)],
            buf.at[c % 2, pl.ds(pl.multiple_of(t * pitch, pitch), pitch)],
            sem.at[c % 2])

    def start_slot(c):
        # tok_s holds the slot's named tokens first, counts_ref how many
        def body(n, carry):
            t = tok_s[0, c * block + n]
            row_copy(c, pos_s[0, t * slots + c], t).start()
            return carry
        lax.fori_loop(0, counts_ref[i * slots + c], body, 0)

    def await_slot(c):
        # every copy of a slot moves one row: as many waits as rows were named
        def body(_, carry):
            row_copy(c, 0, 0).wait()
            return carry
        lax.fori_loop(0, counts_ref[i * slots + c], body, 0)

    def add_slot(c):
        def eight_tokens(g, carry):
            t0 = pl.multiple_of(g * _SUBLANES, _SUBLANES)
            named = pos_v[pl.ds(t0, _SUBLANES), c:c + 1] >= 0
            w = w_v[pl.ds(t0, _SUBLANES), c:c + 1]
            for j in range(chunks):
                lanes = slice(j * _LANES, (j + 1) * _LANES)
                rows = buf[c % 2, pl.ds(t0 * pitch + j, _SUBLANES,
                                        stride=pitch), :]
                out_ref[pl.ds(t0, _SUBLANES), lanes] += jnp.where(
                    named, w * rows, 0.0)
            return carry
        lax.fori_loop(0, block // _SUBLANES, eight_tokens, 0)

    out_ref[...] = jnp.zeros_like(out_ref)
    start_slot(0)
    for c in range(slots):
        if c + 1 < slots:
            start_slot(c + 1)   # in flight while slot c is summed

        @pl.when(counts_ref[i * slots + c] > 0)
        def _():
            await_slot(c)
            add_slot(c)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "vma"))
def _pallas(rows, pos, weight, *, block: int, interpret: bool, vma):
    """The kernel's call, under a ``jit`` of its own: a model calls it at one
    shape forward and backward in every sparse layer, and a nested ``jit`` is
    traced and lowered once a shape where a bare ``pallas_call`` is traced and
    lowered (half a second of Python) at every call, in every process."""
    tokens, slots = pos.shape
    width = rows.shape[1]
    chunks, pitch = width // _LANES, _pitch(width)
    blocks = tokens // block
    if pitch != chunks:   # whatever fills a row's last tile is never summed
        rows = jnp.pad(rows.reshape(-1, chunks, _LANES),
                       ((0, 0), (0, pitch - chunks), (0, 0)))
    named = (pos >= 0).reshape(blocks, block, slots).swapaxes(1, 2)
    counts = jnp.sum(named, axis=2, dtype=jnp.int32).reshape(-1)
    # per block and slot, the tokens that name a row, in token order: the
    # n-th of them by a compare and a sum (a sort of the same costs 0.4 ms)
    token = jnp.arange(block, dtype=jnp.int32)
    rank = jnp.cumsum(named, axis=2, dtype=jnp.int32) - 1
    nth = named[..., None, :] & (rank[..., None, :] == token[:, None])
    tokens_first = jnp.sum(jnp.where(nth, token, 0), axis=3)
    by_block = lambda i, counts_ref: (i, 0)
    scalars = pl.BlockSpec((None, 1, block * slots),
                           lambda i, counts_ref: (i, 0, 0),
                           memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(blocks,),
        in_specs=[
            scalars, scalars,
            pl.BlockSpec((block, slots), by_block),
            pl.BlockSpec((block, slots), by_block),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block, width), by_block),
        scratch_shapes=[
            pltpu.VMEM((2, block * pitch, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block=block, slots=slots, chunks=chunks,
                          pitch=pitch),
        out_shape=jax.ShapeDtypeStruct((tokens, width), jnp.float32, vma=vma),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="moe_combine",   # benchmark/layer_metrics reads the trace by it
        interpret=interpret,
    )(counts, pos.reshape(blocks, 1, block * slots),
      tokens_first.reshape(blocks, 1, block * slots), pos, weight,
      rows.reshape(-1, _LANES))


def _xla(rows, pos, weight):
    """The same sum in the same order as one gather a slot."""
    out = jnp.zeros((pos.shape[0], rows.shape[1]), jnp.float32)
    for c in range(pos.shape[1]):
        named = pos[:, c] >= 0
        picked = rows[jnp.where(named, pos[:, c], 0)].astype(jnp.float32)
        out = out + jnp.where(named[:, None], weight[:, c, None] * picked,
                              0.0)
    return out


def gather_sum(rows: jax.Array, pos: jax.Array,
               weight: jax.Array) -> jax.Array:
    """``out[t] = sum_c weight[t, c] * rows[pos[t, c]]`` over the slots with
    ``pos[t, c] >= 0``, summed in slot order in float32: ``[tokens, D]``.

    ``rows``: ``[R, D]``; ``pos``: ``[tokens, slots]`` int32, a row of
    ``rows`` or a negative number for a slot that adds nothing; ``weight``:
    ``[tokens, slots]`` float32. No row that no slot names is read. Not
    differentiable: the expert layer's custom VJP writes its transpose (a
    gather of rows) itself."""
    block = plan(pos.shape[0], rows.shape[1], rows.dtype)
    weight = weight.astype(jnp.float32)
    if block is None:
        _trace.note_fallback(
            "moe_combine",
            _refusal(pos.shape[0], rows.shape[1], rows.dtype)
            or "tokens_not_whole_blocks",
            tokens=pos.shape[0], slots=pos.shape[1], width=rows.shape[1])
        return _xla(rows, pos, weight)
    return _pallas(rows, pos, weight, block=block,
                   interpret=_pa._resolve_interpret(None),
                   vma=_pa._vma(rows, pos, weight))
