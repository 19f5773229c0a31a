"""Expert parallelism (Mixture-of-Experts) over an ``expert`` mesh axis.

TPU-native extension beyond the reference framework: the reference's op set
has no alltoall at all (``horovod/common/message.h:48-50`` — allreduce,
allgather, broadcast only) and no model-structure code (SURVEY.md §2.3), so
MoE training is impossible there. Here expert parallelism composes with the
data axis on one mesh: tokens are routed top-1 (Switch style) with a static
capacity so every shape stays compile-time constant, dispatched to expert
owners with ``lax.all_to_all`` riding ICI, transformed by the local expert
FFNs in one batched einsum (MXU-friendly), and combined back.

Design notes (the GShard/Switch dispatch pattern, re-derived for shard_map):
 - dispatch/combine are dense one-hot tensors ``[tokens, experts, capacity]``
   — no gathers with data-dependent shapes, so XLA tiles everything.
 - per-device expert compute is a single ``[E_local, n_send*C, D]`` batched
   matmul — large, static, bfloat16-friendly.
 - the auxiliary load-balancing loss is the standard mean(gates)*mean(mask)
   dot product per expert, summed over experts, scaled by E.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import trace as _trace
from ..common.compat import axis_size as _axis_size
from ..ops import moe_combine as _combine
from .mesh import DATA_AXIS, EXPERT_AXIS


class MoEParams(NamedTuple):
    """Parameters of one MoE FFN layer.

    ``w_router`` is replicated; ``w_in``/``w_out`` hold only the experts
    owned by this device along the ``expert`` axis (shard_map view) —
    globally they are sharded ``P(expert_axis)`` on dim 0.
    """

    w_router: jax.Array  # [D, E_total]
    w_in: jax.Array      # [E_local, D, H]
    w_out: jax.Array     # [E_local, H, D]


def init_moe_params(
    rng: jax.Array,
    *,
    d_model: int,
    d_hidden: int,
    num_experts: int,
    num_expert_shards: int,
    dtype=jnp.float32,
) -> MoEParams:
    """Initialize *global* MoE params (callers shard w_in/w_out over the
    expert axis; dim 0 of both is the global expert count)."""
    if num_experts % num_expert_shards:
        raise ValueError(
            f"num_experts={num_experts} not divisible by "
            f"expert shards={num_expert_shards}"
        )
    kr, ki, ko = jax.random.split(rng, 3)
    scale_in = 1.0 / jnp.sqrt(d_model)
    scale_out = 1.0 / jnp.sqrt(d_hidden)
    return MoEParams(
        w_router=(jax.random.normal(kr, (d_model, num_experts)) * scale_in
                  ).astype(dtype),
        w_in=(jax.random.normal(ki, (num_experts, d_model, d_hidden))
              * scale_in).astype(dtype),
        w_out=(jax.random.normal(ko, (num_experts, d_hidden, d_model))
               * scale_out).astype(dtype),
    )


def moe_ffn(
    params: MoEParams,
    x: jax.Array,
    *,
    expert_axis: str = EXPERT_AXIS,
    capacity_factor: float = 1.25,
    activation: Callable = jax.nn.gelu,
) -> Tuple[jax.Array, jax.Array]:
    """Apply the expert-parallel MoE FFN to local tokens ``x`` ``[S, D]``.

    Must run inside ``shard_map`` with a mesh that has ``expert_axis``.
    Returns ``(y [S, D], aux_loss scalar)``. Every device routes its own
    S tokens over ALL ``E_total`` experts; token shards travel to the
    expert's owner via all_to_all and come back combined.
    """
    n_exp = _axis_size(expert_axis)
    e_local, d_model, _ = params.w_in.shape
    e_total = e_local * n_exp
    s_tokens = x.shape[0]
    # Static capacity per (expert, source-device): how many of this
    # device's tokens one expert may accept this step. Overflow tokens
    # drop to the residual path (standard Switch behavior).
    capacity = max(1, int(capacity_factor * s_tokens / e_total))

    # --- routing (top-1 / Switch) ---
    logits = x @ params.w_router  # [S, E_total]
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_index = jnp.argmax(gates, axis=-1)              # [S]
    gate = jnp.take_along_axis(
        gates, expert_index[:, None], axis=-1
    )[:, 0]                                                # [S]

    # Position of each token within its expert's capacity buffer.
    onehot = jax.nn.one_hot(expert_index, e_total, dtype=jnp.float32)
    position = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # [S, E_total]
    keep = (position < capacity) & (onehot > 0)
    pos = jnp.where(keep, position, 0.0).astype(jnp.int32)

    # Load-balancing auxiliary loss (Switch eq. 4).
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(gates, axis=0)
    aux_loss = e_total * jnp.sum(frac_tokens * frac_probs)

    # Dense dispatch/combine tensors [S, E_total, C].
    pos_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
    dispatch = pos_onehot * keep.astype(jnp.float32)[..., None]
    combine = dispatch * gate[:, None, None]

    # [S, E, C] x [S, D] -> [E, C, D]: each expert's capacity buffer.
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, x.astype(jnp.float32))

    # --- all_to_all: send each expert-shard group to its owner ---
    # [E_total, C, D] -> [n_exp, E_local, C, D]; peer p owns experts
    # [p*E_local, (p+1)*E_local).
    expert_in = expert_in.reshape(n_exp, e_local, capacity, d_model)
    # After the exchange dim 0 indexes the *source* device.
    expert_in = lax.all_to_all(
        expert_in, expert_axis, split_axis=0, concat_axis=0, tiled=False
    )  # [n_exp, E_local, C, D]

    # --- expert compute: one batched matmul over local experts ---
    # Fold (source-device, capacity) into one token dim per expert.
    h = jnp.einsum(
        "pecd,edh->pech", expert_in.astype(x.dtype), params.w_in
    )
    h = activation(h)
    out = jnp.einsum("pech,ehd->pecd", h, params.w_out)

    # --- return trip + combine ---
    out = lax.all_to_all(
        out.astype(jnp.float32), expert_axis,
        split_axis=0, concat_axis=0, tiled=False,
    )  # [n_exp, E_local, C, D] with dim 0 = owner again
    out = out.reshape(e_total, capacity, d_model)
    y = jnp.einsum("sec,ecd->sd", combine, out)
    return y.astype(x.dtype), aux_loss


# --------------------------------------------------------------------------
# Dropless top-k layer: this device's experts' part of the result.
# --------------------------------------------------------------------------

def route_top_k(x, w_router, *, top_k: int, norm_topk: bool = True,
                score: str = "softmax", select_bias=None,
                norm_eps: float = 0.0, scale: float = 1.0,
                n_group: int = 1, topk_group: int = 1):
    """Routing over ALL experts: ``(weights [S, k] f32, expert ids [S, k]
    int32)``. The router's product and score are float32 at full precision,
    whatever ``x`` is: a token whose k-th and (k+1)-th scores are near a tie
    must choose as the float32 model does.

    ``score`` is ``"softmax"`` over the experts or ``"sigmoid"`` of each.
    ``select_bias`` (``[E_total]``) is added to the scores for the CHOICE
    only: the weights are the chosen experts' scores without it, and it
    takes no gradient. With ``norm_topk`` the weights are divided by their
    sum plus ``norm_eps``; ``scale`` multiplies them last. With ``n_group``
    over 1 the choice is group-limited (DeepSeek-V3's ``noaux_tc``): the
    experts lie in ``n_group`` groups of consecutive ids, a group's score is
    the sum of its two best scores (bias included), and only the experts of
    the ``topk_group`` best groups can be chosen. The defaults are softmax,
    no bias, no epsilon, scale 1 and no groups."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"score is 'softmax' or 'sigmoid', not {score!r}")
    e_total = w_router.shape[-1]
    if n_group > 1 and (e_total % n_group or e_total // n_group < 2
                        or not 0 < topk_group <= n_group
                        or topk_group * (e_total // n_group) < top_k):
        raise ValueError(
            f"{e_total} experts in {n_group} groups of which {topk_group} "
            f"are kept cannot give a token {top_k} experts")
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    if select_bias is None and n_group == 1:
        weights, ids = lax.top_k(scores, top_k)
    else:
        choice = scores if select_bias is None else scores + lax.stop_gradient(
            select_bias.astype(jnp.float32))
        if n_group > 1:
            choice = _keep_best_groups(choice, n_group, topk_group)
        _, ids = lax.top_k(choice, top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + norm_eps if norm_eps else total)
    if scale != 1.0:
        weights = weights * scale
    return weights, ids.astype(jnp.int32)


def _keep_best_groups(choice, n_group: int, topk_group: int):
    """``choice`` (``[S, E]``) with ``-inf`` at every expert outside the
    ``topk_group`` groups whose two best entries sum highest (on a tie the
    group of the lower id, as ``lax.top_k`` orders)."""
    grouped = choice.reshape(choice.shape[0], n_group, -1)
    group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
    _, best = lax.top_k(group_score, topk_group)
    kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=-2)
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(choice.shape)


def _held_groups(ids, first_expert: int, experts_held: int):
    """``(key [S * k], sizes [experts_held])``: per (token, expert) choice the
    held expert's local id (``experts_held`` for an expert that lives
    elsewhere), and how many choices fall on each held expert."""
    local = ids.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < experts_held), local,
                    experts_held)
    # a compare and a sum, not ``bincount``: that is a scatter-add of S * k
    # ones, which the chip applies one after another (1.2 ms for 131072)
    sizes = jnp.sum(key[:, None] == jnp.arange(experts_held), axis=0,
                    dtype=jnp.int32)
    return key, sizes


def held_load(ids, *, first_expert: int, experts_held: int):
    """``(pairs, largest)``: how many (token, expert) choices of ``ids`` fall
    on the ``experts_held`` experts from ``first_expert`` on (what the
    layer's grouped products compute), and the load of the busiest of them."""
    _, sizes = _held_groups(ids, first_expert, experts_held)
    return jnp.sum(sizes), jnp.max(sizes)


_RIDGE_ROWS = 256   # rows an expert: see _tile_plan


def _tile_plan(s_tokens: int, top_k: int, e_held: int, e_total: int):
    """``(first, over, n_over)``: the rows of the first tile, of an overflow
    tile, and how many overflow tiles the worst case (every token choosing
    only held experts) needs behind the first; in whole 512s, a function of
    the shapes and of nothing else. Which side is cheap depends on the rows
    an expert gets. From some 240 rows an expert up (v5e) its products are
    compute-bound and a row past the load costs what a row costs: the first
    tile is what balanced routing gives the held experts and a quarter
    more, an overflow tile an eighth of it (4096 rows an expert: 38% fewer
    rows took 28 of 714 ms off the step, PERF.md section 6, PR 39). Under
    that a product is bound by reading the expert's weights and a tile
    costs that read however few its rows: tiles are as many rows as
    balanced routing fills twice over (160 rows an expert: 38% fewer rows
    saved 0.9 ms of 303, and a load that wanders to twice the balanced one
    within a window met an overflow tile at more than that)."""
    worst = s_tokens * min(top_k, e_held)
    balanced = s_tokens * top_k * e_held // e_total
    if balanced < _RIDGE_ROWS * e_held:
        first = over = _round_up(2 * balanced + 8 * e_held, 512)
    else:
        first = _round_up(balanced + balanced // 4, 512)
        over = _round_up(balanced // 8, 512)
    first = min(worst, first)
    return first, over, -(-(worst - first) // over)


def _tiles_needed(load, first: int, over: int):
    """The tiles a load of ``load`` sorted pairs computes: the first always,
    and as many overflow tiles as hold what lies past it."""
    return 1 + (jnp.maximum(load - first, 0) + over - 1) // over


def _load_and_tiles(ids, first_expert: int, experts_held: int,
                    experts_total: int):
    """``[pairs, largest, tiles]`` int32 for one layer's choices ``ids``
    (``[S, k]``): :func:`held_load`, and how many tiles :func:`dropless_moe`
    computes for that load, by the arithmetic its loop's length uses."""
    pairs, largest = held_load(ids, first_expert=first_expert,
                               experts_held=experts_held)
    first, over, _ = _tile_plan(*ids.shape, experts_held, experts_total)
    return jnp.stack([pairs, largest, _tiles_needed(pairs, first, over)])


def _tile(order, lo, rows: int, starts, ends):
    """The tile of ``rows`` rows from row ``lo`` of the sorted pairs: its
    pairs and its part of every group."""
    sizes = jnp.clip(jnp.minimum(ends, lo + rows) - jnp.maximum(starts, lo), 0)
    return lax.dynamic_slice(order, (lo,), (rows,)), sizes


def relu_squared(x):
    """``relu(x) ** 2``: the activation of an expert without a gate."""
    return jnp.square(jax.nn.relu(x))


def _tile_products(xs, w_gate, w_up, w_down, sizes, activation):
    """The grouped products over a tile's part of the ragged assignment,
    ``down(activation(gate x) * up x)`` or, for experts without a gate
    (``w_gate`` None), ``down(activation(up x))``: ``[rows, D]`` float32.
    Rows past the last group belong to no expert, and
    a grouped product leaves whatever was in memory there (zeros on the CPU,
    anything on the chip), forward AND transposed: nothing may read them.
    The per-token sums below read the rows that :func:`_token_slots` names,
    which are held pairs' rows and no others."""
    with jax.named_scope(_trace.SCOPE_MOE_EXPERTS):
        grouped = lambda a, w: lax.ragged_dot(
            a, w, sizes, preferred_element_type=jnp.float32)
        if w_gate is None:
            h = activation(grouped(xs, w_up))
        else:
            h = activation(grouped(xs, w_gate)) * grouped(xs, w_up)
        return grouped(h.astype(w_down.dtype), w_down)      # [rows, D] f32


def _token_slots(pos, lo, rows: int, load, slots, weight=None):
    """Where a tile's rows go, token-major. ``pos``: ``[S, k]``, every
    pair's row in the sorted order; the tile is the ``rows`` rows from row
    ``lo``. Returns ``(slot_pos [S, slots], slot_weight [S, slots])``: per
    token its pairs that are held AND lie in the tile, moved to the first
    slots in pair order: the pair's row inside the tile, or -1 for a slot
    that holds none, and its weight (1 without ``weight``). ``slots`` is the
    most held pairs a token can have."""
    local = pos - lo
    mine = (local >= 0) & (local < jnp.minimum(rows, load - lo))
    rank = jnp.cumsum(mine, axis=1, dtype=jnp.int32) - 1
    pick = mine[:, :, None] & (rank[:, :, None] == jnp.arange(slots))
    slot_pos = jnp.max(jnp.where(pick, local[:, :, None], -1), axis=1)
    if weight is None:
        return slot_pos, (slot_pos >= 0).astype(jnp.float32)
    return slot_pos, jnp.sum(jnp.where(pick, weight[:, :, None], 0.0), axis=1)


def _walk_tiles(tile, plan, load):
    """``tile(lo, rows)`` of the first tile of ``plan`` (:func:`_tile_plan`)
    and, added to it leaf by leaf, of every overflow tile that ``load``
    sorted pairs reach. The loop's length is read on the device; its body is
    the overflow tile's program, a second, smaller one than the first
    tile's."""
    first, over, n_over = plan
    head = tile(0, first)
    if not n_over:                  # the first tile holds the worst case
        return head
    return lax.fori_loop(
        1, _tiles_needed(load, first, over),
        lambda i, acc: jax.tree.map(
            jnp.add, acc, tile(first + (i - 1) * over, over)),
        head)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _experts(x, scale, w_gate, w_up, w_down, order, pos, starts, ends, plan,
             slots, activation):
    """The held experts' part of the result for the sorted pairs ``order``
    (flat: the first tile's rows, then the overflow tiles', as ``plan`` of
    :func:`_tile_plan` cuts them; ``pos`` ``[S, k]`` is its inverse): the
    first tile always, overflow tiles in a loop that runs as far as this
    batch's load reaches.
    A tile's rows are gathered by pair, multiplied by group, and summed per
    token by a gather-sum (``ops/moe_combine``): each token reads its held
    pairs' rows, weighted, in pair order. The loop's length is read on the
    device, so it has no transpose of JAX's: the backward below walks the
    same tiles again, one tile's rows alive at a time."""
    def tile_sum(lo, rows):
        order_t, sizes_t = _tile(order, lo, rows, starts, ends)
        with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
            xs = x[order_t // scale.shape[1]].astype(w_up.dtype)
        ys = _tile_products(xs, w_gate, w_up, w_down, sizes_t, activation)
        with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
            slot_pos, slot_w = _token_slots(
                pos, lo, rows, ends[-1], slots, scale)
            return _combine.gather_sum(ys, slot_pos, slot_w)

    return _walk_tiles(tile_sum, plan, ends[-1])


def _experts_fwd(x, scale, w_gate, w_up, w_down, order, pos, starts, ends,
                 plan, slots, activation):
    y = _experts(x, scale, w_gate, w_up, w_down, order, pos, starts, ends,
                 plan, slots, activation)
    return y, (x, scale, w_gate, w_up, w_down, order, pos, starts, ends)


def _experts_bwd(plan, slots, activation, res, dy):
    x, scale, w_gate, w_up, w_down, order, pos, starts, ends = res
    top_k = scale.shape[1]
    f32 = lambda tree: jax.tree.map(lambda g: g.astype(jnp.float32), tree)

    def tile_grads(lo, rows):
        order_t, sizes_t = _tile(order, lo, rows, starts, ends)
        with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
            token = order_t // top_k
            xs = x[token].astype(w_up.dtype)
        # the tile's forward again: its rows are in no residual
        ys, vjp = jax.vjp(
            lambda *a: _tile_products(*a, sizes_t, activation),
            xs, w_gate, w_up, w_down)
        with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
            # y[t] = sum over t's held pairs of weight * ys[row]: a row's
            # cotangent is its token's dy times the pair's weight, the
            # weight's is the row's product with dy. Rows past the load get
            # weight 0 and hand the transposed products zeros.
            valid = jnp.arange(rows) < jnp.sum(sizes_t)
            weight = jnp.where(valid, scale.reshape(-1)[order_t], 0.0)
            dy_rows = dy[token]
            dweight = jnp.where(valid, jnp.sum(ys * dy_rows, axis=-1), 0.0)
            dys = dy_rows * weight[:, None]
        dxs, *dw = vjp(dys)
        with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
            slot_pos, slot_one = _token_slots(pos, lo, rows, ends[-1], slots)
            dx = _combine.gather_sum(dxs.astype(jnp.float32), slot_pos,
                                     slot_one)
            # rows past the load carry 0, whatever pair they stand for
            dscale = jnp.zeros(scale.size, jnp.float32).at[order_t].add(
                dweight).reshape(scale.shape)
        return (dx, dscale, *f32(dw))

    grads = _walk_tiles(tile_grads, plan, ends[-1])
    primals = (x, scale, w_gate, w_up, w_down)
    # an expert without a gate has no gate's gradient: None, like its weight
    return jax.tree.map(lambda g, p: g.astype(p.dtype), grads, primals) + (
        None, None, None, None)


_experts.defvjp(_experts_fwd, _experts_bwd)


def dropless_moe(
    x: jax.Array,
    w_router: jax.Array,
    w_gate: Optional[jax.Array],
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int,
    first_expert: int = 0,
    norm_topk: bool = True,
    score: str = "softmax",
    select_bias=None,
    norm_eps: float = 0.0,
    scale: float = 1.0,
    n_group: int = 1,
    topk_group: int = 1,
    activation: Callable = jax.nn.silu,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """This device's experts' part of a top-k MoE feed-forward, no token
    dropped and no capacity set.

    ``x``: ``[S, D]`` local tokens. ``w_router``: ``[D, E_total]``,
    replicated. ``w_gate``/``w_up``: ``[E_held, D, F]`` and ``w_down``:
    ``[E_held, F, D]``: the experts ``first_expert .. first_expert +
    E_held`` that live here. Every token is routed over all ``E_total``
    experts by :func:`route_top_k` (softmax and top-k by default, weights
    normalised over all k when ``norm_topk``; ``score``, ``select_bias``,
    ``norm_eps``, ``scale``, ``n_group`` and ``topk_group`` are its), and
    the sum ``sum_k w_k *
    down_e(activation(gate_e(x)) * up_e(x))`` runs over the chosen experts that
    are held here (``w_gate`` None: experts without a gate, ``down_e(
    activation(up_e(x)))``, two grouped products a tile and two weights
    through the backward; :func:`relu_squared` is such a model's
    ``activation``); what the
    others would add belongs to their owners (across an ``expert`` axis the
    exchange that brings their tokens here is ROADMAP's debt; on one chip
    the layer runs without it). Returns ``[S, D]`` float32.

    The (token, expert) pairs held here are sorted by expert and multiplied
    by grouped products over the ragged assignment (``lax.ragged_dot``): no
    ``[tokens, experts, capacity]`` tensor exists. Shapes are static, so the
    sorted pairs are cut into a first tile, always computed, and overflow
    tiles behind it, of which a loop computes as many as this batch's load
    reaches (:func:`held_load`). Where an expert gets rows enough for its
    products to be compute-bound the first tile is the rows balanced
    routing fills and a quarter more and an overflow tile an eighth of
    them, so the rows computed follow the load and a load one row past the
    first tile costs one small tile; where it gets few, rows are cheap and
    tiles are not, and every tile is as many rows as balanced routing fills
    twice over (:func:`_tile_plan`). Every token choosing only held experts
    is computed in full, tile by tile. A token's result is the sum of its held
    pairs' rows, tile by tile, each read
    where the sort put it and weighted as it is added, in pair order
    (``ops/moe_combine.gather_sum``): no row is scattered, no row past the
    load is read, and the same sum with unit weights gives the tokens'
    gradient from the transposed products' rows."""
    s_tokens, d_model = x.shape
    e_total = w_router.shape[-1]
    e_held = w_up.shape[0]
    plan = first, over, n_over = _tile_plan(s_tokens, top_k, e_held, e_total)
    rows_in_all = first + n_over * over
    slots = min(top_k, e_held)      # the most held pairs a token can have
    block = _combine.plan(s_tokens, d_model, jnp.float32)
    _trace.note_plan(
        moe_experts_total=e_total, moe_experts_held=e_held,
        moe_top_k=top_k, moe_tile_rows=first, moe_tiles=1 + n_over,
        moe_overflow_rows=over,
        moe_score=score, moe_select_bias=select_bias is not None,
        moe_groups=n_group, moe_groups_kept=topk_group,
        moe_gated=w_gate is not None,
        moe_combine_kernel=block is not None,
        moe_combine_block=block or 0, moe_combine_slots=slots,
    )
    with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
        weights, ids = route_top_k(
            x, w_router, top_k=top_k, norm_topk=norm_topk, score=score,
            select_bias=select_bias, norm_eps=norm_eps, scale=scale,
            n_group=n_group, topk_group=topk_group)
        key, sizes = _held_groups(ids, first_expert, e_held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        pos = jnp.argsort(order).astype(jnp.int32)    # the sort's inverse
        order = jnp.pad(order[:rows_in_all],
                        (0, max(0, rows_in_all - order.size)))
        ends = jnp.cumsum(sizes)
    with jax.named_scope(_trace.SCOPE_MOE_EXPERTS):
        # cast once, outside the loop over tiles
        w_gate, w_up, w_down = jax.tree.map(
            lambda w: w.astype(dtype), (w_gate, w_up, w_down))
    return _experts(x, weights, w_gate, w_up, w_down, order,
                    pos.reshape(ids.shape), ends - sizes, ends, plan, slots,
                    activation)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def expert_sharding_specs(tree, expert_axis: str = EXPERT_AXIS):
    """PartitionSpecs for a pytree: ``MoEParams.w_in``/``w_out`` leaves
    shard over ``expert_axis`` (dim 0 = global expert id), everything else
    replicated. Works for params and for optimizer state that mirrors the
    param structure (optax momentum etc.)."""
    def spec(path, _):
        return P(expert_axis) if _is_expert_leaf(path) else P()

    return jax.tree_util.tree_map_with_path(spec, tree)


def _is_expert_leaf(path) -> bool:
    return any(getattr(p, "name", None) in ("w_in", "w_out") for p in path)


def make_ep_train_step(
    loss_fn: Callable,
    optimizer,
    mesh: Mesh,
    params,
    opt_state,
    *,
    batch_spec=None,
    data_axis: str = DATA_AXIS,
    expert_axis: str = EXPERT_AXIS,
    aux_loss_weight: float = 0.01,
    donate: bool = True,
):
    """Build a jitted DP x EP train step.

    ``loss_fn(params, batch) -> (task_loss, aux_loss)`` runs on the local
    batch shard and calls :func:`moe_ffn` somewhere inside. ``params`` /
    ``opt_state`` are example pytrees (structure only) where
    ``MoEParams.w_in``/``w_out`` are sharded ``P(expert_axis)`` and
    everything else is replicated. The batch dim shards over BOTH axes by
    default (``P((data, expert))`` — every device holds distinct tokens;
    the expert group exchanges real work via all_to_all rather than
    duplicating it). Gradients of replicated params reduce over both axes;
    expert-sharded gradients reduce over ``data`` only (each expert shard
    has exactly one owner per data replica).
    """
    if batch_spec is None:
        batch_spec = P((data_axis, expert_axis))
    from ..jax import _shard_map

    def step(params, opt_state, batch):
        def total_loss(p):
            task, aux = loss_fn(p, batch)
            return task + aux_loss_weight * aux, (task, aux)

        (_, (task, aux)), grads = jax.value_and_grad(
            total_loss, has_aux=True
        )(params)

        def reduce_grad(path, g):
            g = lax.pmean(g, data_axis)
            if _is_expert_leaf(path):
                # The all_to_all transpose already SUMMED cotangents from
                # every device in the expert group into the owner's shard;
                # divide so expert grads share the replicated params' scale
                # (grad of the loss pmean'd over both axes).
                g = g / _axis_size(expert_axis)
            else:
                g = lax.pmean(g, expert_axis)
            return g

        grads = jax.tree_util.tree_map_with_path(reduce_grad, grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda a, u: a + u, params, updates)
        return params, opt_state, lax.pmean(task, (data_axis, expert_axis))

    param_specs = expert_sharding_specs(params, expert_axis)
    opt_specs = expert_sharding_specs(opt_state, expert_axis)
    fn = _shard_map(
        step, mesh,
        in_specs=(param_specs, opt_specs, batch_spec),
        out_specs=(param_specs, opt_specs, P()),
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())
