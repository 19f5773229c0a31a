"""Plain float32 reference of the Nemotron-H-style hybrid decoder: no kernel,
no chunk, no sort, no cache. It imports nothing of the program.

As the published ``config.json`` and the family's reference code give the
layers (``n(x) = w * x * rsqrt(mean(x^2) + layer_norm_epsilon)``, ``w`` from
ones). Layer ``i`` is ``x + f(n(x))`` with ``f`` what letter ``i`` of
``hybrid_override_pattern`` says:

- ``M``, Mamba-2 (``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
  state ``N = ssm_state_size``, ``G = n_groups`` groups): ``[z | xBC | dt] =
  in_proj(u)`` (``H P``, ``H P + 2 G N`` and ``H`` columns); ``xBC =
  silu(conv(xBC) + bias)``, depthwise, causal, ``conv_kernel`` taps, zeros
  before the sequence; ``xBC -> x [H, P] | B [G, N] | C [G, N]``; ``dt =
  softplus(dt + dt_bias)``, ``a = exp(-dt * exp(A_log))``; per head ``h`` of
  group ``h // (H / G)``, token by token from a zero state: ``S = a S + dt x
  B^T``, ``y = S C + D x``; ``y = y * silu(z)``, RMS-normalised over each
  group's ``H P / G`` channels, times a weight; ``out_proj``;
- ``*``, attention: ``q_proj`` gives ``num_attention_heads`` heads,
  ``k_proj``/``v_proj`` ``num_key_value_heads``, of ``head_dim``; no bias, no
  norm and no rotary positions; causal softmax at scale ``head_dim ** -0.5``,
  key/value head ``j`` serving query heads ``g j .. g j + g - 1``; ``out_proj``;
- ``E``, experts: ``s = sigmoid(router(u))`` over all experts in float32;
  ``ids = top_k(s + expert_bias)`` (``n_group`` 1: no group limit); ``g =
  s[ids]``; ``g = routed_scaling_factor * g / (sum(g) + 1e-20)``; the sum
  over the chosen experts that are HELD (``first_expert_held .. +
  n_routed_experts``: the chip's share, as the configuration file states) of
  ``g * down(relu(up(u))^2)``, a dense loop over the held experts with masks;
  plus the shared expert, the same form at its own width, unweighted. The
  bias enters the choice only, so its gradient is exactly zero;
- after the last layer ``norm_f`` and the untied head over the vocabulary
  slice; mean cross entropy.

Departures from a literal transcription, none of which changes a value: the
Mamba-2 mixer is computed ``GROUPS_AT_ONCE`` groups of heads at a time (its
groups do not meet before ``out_proj``), the recurrence is checkpointed every
``SEGMENT`` tokens, attention runs in blocks of ``ROWS`` query rows, the experts under a scan with a checkpoint each, the
head and the loss ``HEAD_ROWS`` positions at a time, and each layer is
recomputed in the backward, so that one sequence fits the chip beside the
harness's own state (``block_rows``). Every product goes through
``precision.matmul`` / ``precision.operand`` so that the int8 control rounds
both operands of all of them, the taps and the recurrence's included; the
router's product stays float32 at full precision in every precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .precision import HIGHEST, matmul, operand

GROUPS_AT_ONCE = 2  # groups of heads the Mamba-2 mixer is computed in
SEGMENT = 64      # tokens between two checkpoints of the recurrence
ROWS = 128        # query rows of attention computed at a time
HEAD_ROWS = 1024  # positions of the head and the loss computed at a time
ROUTE_NORM_EPS = 1e-20  # under the chosen weights' sum


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=HIGHEST)


def _rms(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps))


def _selective_scan(x, dt, A, B, C, D, precision):
    """Token by token; ``x``: ``[b, T, H, P]``, ``dt``: ``[b, T, H]``, ``A``
    and ``D``: ``[H]``, ``B`` and ``C``: ``[b, T, H, N]`` (a group's, repeated
    to its heads). Returns ``y``: ``[b, T, H, P]``."""
    b, T, H, P = x.shape
    N = B.shape[-1]
    seg = min(SEGMENT, T)

    def token(S, t):
        x_t, dt_t, B_t, C_t = t
        S = S * jnp.exp(dt_t * A)[..., None, None] + _einsum(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, B_t, precision)
        y = _einsum("bhpn,bhn->bhp", S, C_t, precision)
        return S, y + operand(D, precision)[:, None] * operand(x_t, precision)

    @jax.checkpoint
    def segment(S, ts):
        return jax.lax.scan(token, S, ts)

    # time leads, in segments: [T / seg, seg, b, H, ...]
    lead = lambda m: jnp.moveaxis(m, 1, 0).reshape(
        (T // seg, seg) + m.shape[:1] + m.shape[2:])
    _, y = jax.lax.scan(segment, jnp.zeros((b, H, P, N), jnp.float32),
                        tuple(lead(m) for m in (x, dt, B, C)))
    return jnp.moveaxis(y.reshape((T, b, H, P)), 0, 1)


def _mamba(u, p, cfg, precision):
    """The mixer, ``GROUPS_AT_ONCE`` groups at a time: a group's heads read
    its ``B`` and ``C`` and are normed together, and groups do not meet
    before ``out_proj``, whose rows a group's output multiplies, so the sum
    over groups is the layer. (All groups at once is the literal
    transcription; a few at a time keep their activations and states, not
    the layer's, alive at once.)"""
    b, T, d = u.shape
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    per, inner, bc = H // G, H * P, G * N
    # columns [z | x | B | C | dt], heads and groups contiguous inside each:
    # a group's columns of every part, stacked with the group leading
    at_once = GROUPS_AT_ONCE if G % GROUPS_AT_ONCE == 0 else 1
    batches = lambda w: w.reshape((G // at_once, at_once) + w.shape[1:])
    cols = lambda w, lo, n: batches(jnp.moveaxis(
        w[..., lo:lo + n].reshape(w.shape[:-1] + (G, n // G)), -2, 0))
    w_in, conv = p["in_proj"]["kernel"], p["conv"]
    taps = conv["kernel"].shape[0]
    heads = lambda v: batches(v.reshape(G, per))
    parts = {
        "wz": cols(w_in, 0, inner), "wx": cols(w_in, inner, inner),
        "wB": cols(w_in, 2 * inner, bc), "wC": cols(w_in, 2 * inner + bc, bc),
        "wdt": cols(w_in, 2 * inner + 2 * bc, H),
        "kx": cols(conv["kernel"], 0, inner),
        "kB": cols(conv["kernel"], inner, bc),
        "kC": cols(conv["kernel"], inner + bc, bc),
        "bx": cols(conv["bias"], 0, inner), "bB": cols(conv["bias"], inner, bc),
        "bC": cols(conv["bias"], inner + bc, bc),
        "A_log": heads(p["A_log"]), "dt_bias": heads(p["dt_bias"]),
        "D": heads(p["D"]),
        "scale": batches(p["norm"]["scale"].reshape(G, per * P)),
        "wo": batches(p["out_proj"]["kernel"].reshape(G, per * P, d)),
    }

    def conv_silu(x, w, bias):  # s_t = sum_j w[j] x_{t - (taps - 1) + j}
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        s = 0.0
        for j in range(taps):
            s = s + operand(padded[:, j:j + T], precision) * operand(
                w[j], precision)
        return jax.nn.silu(s + bias)

    def group(g):
        proj = lambda w, k, bias: conv_silu(matmul(u, w, precision), k, bias)
        x = proj(g["wx"], g["kx"], g["bx"]).reshape(b, T, per, P)
        # the group's B and C, read by each of its heads
        shared = lambda m: jnp.broadcast_to(m[:, :, None], (b, T, per, N))
        dt = jax.nn.softplus(matmul(u, g["wdt"], precision) + g["dt_bias"])
        o = _selective_scan(
            x, dt, -jnp.exp(g["A_log"]),
            shared(proj(g["wB"], g["kB"], g["bB"])),
            shared(proj(g["wC"], g["kC"], g["bC"])), g["D"], precision)
        # the gate first, then the norm over the group's channels
        o = o.reshape(b, T, per * P) * jax.nn.silu(
            matmul(u, g["wz"], precision))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg["layer_norm_epsilon"]) * g["scale"]
        return matmul(o, g["wo"], precision)

    @jax.checkpoint
    def some(y, gs):
        return y + jnp.sum(jax.vmap(group)(gs), axis=0), None

    y, _ = jax.lax.scan(some, jnp.zeros_like(u), parts)
    return y


def _attention(h, p, cfg, precision):
    B, T, _ = h.shape
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    q = matmul(h, p["q_proj"]["kernel"], precision).reshape(B, T, H, D)
    k = matmul(h, p["k_proj"]["kernel"], precision).reshape(B, T, KV, D)
    v = matmul(h, p["v_proj"]["kernel"], precision).reshape(B, T, KV, D)
    group = H // KV
    # query head i reads key/value head i // group
    q = q.reshape(B, T, KV, group, D)
    rows = min(ROWS, T)

    @jax.checkpoint
    def block(start, q_rows):
        s = _einsum("bqjgd,bkjd->bjgqk", q_rows, k, precision) / jnp.sqrt(
            jnp.float32(D))
        pos_q = start + jnp.arange(rows)
        mask = pos_q[:, None] >= jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return start + rows, _einsum("bjgqk,bkjd->bqjgd", probs, v,
                                     precision)

    blocks = jnp.moveaxis(q.reshape(B, T // rows, rows, KV, group, D), 1, 0)
    _, out = jax.lax.scan(block, 0, blocks)
    a = jnp.moveaxis(out, 0, 1).reshape(B, T, H * D)
    return matmul(a, p["out_proj"]["kernel"], precision)


def route(x, p, cfg):
    """``(weights [S, k], ids [S, k])`` of the tokens ``x`` (``[S, d]``).
    The router is float32 at full precision in every precision: which
    experts a token goes to is not a product to be rounded."""
    logits = jnp.matmul(x, p["router"]["kernel"], precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + p["expert_bias"],
                           cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + ROUTE_NORM_EPS)
    return cfg["routed_scaling_factor"] * weights, ids


def _expert(x, w_up, w_down, precision):
    return matmul(jnp.square(jax.nn.relu(matmul(x, w_up, precision))),
                  w_down, precision)


def _moe(h, p, cfg, precision):
    B, T, d = h.shape
    x = h.reshape(B * T, d)
    weights, ids = route(x, p, cfg)

    @jax.checkpoint
    def expert(y, e):
        w_up, w_down, index = e
        # this expert's weight for every token: zero where it was not chosen
        mine = jnp.sum(jnp.where(ids == index, weights, 0.0), axis=-1)
        return y + mine[:, None] * _expert(x, w_up, w_down, precision), None

    ex = p["experts"]
    held = ex["up"].shape[0]
    first = cfg.get("first_expert_held", 0)
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        ex["up"], ex["down"], first + jnp.arange(held, dtype=ids.dtype)))
    y = y + _expert(x, p["shared_up_proj"]["kernel"],
                    p["shared_down_proj"]["kernel"], precision)
    return y.reshape(B, T, d)


_MIXERS = {"M": _mamba, "*": _attention, "E": _moe}


def _layer(x, p, cfg, kind, precision):
    if kind not in _MIXERS:
        raise ValueError(f"no layer kind {kind!r}")
    h = _rms(x, p["norm"]["scale"], cfg["layer_norm_epsilon"])
    return x + _MIXERS[kind](h, p["mixer"], cfg, precision)


def hidden(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, hidden] after the final norm."""
    x = params["embed_tokens"]["embedding"][tokens]
    layer = _layer
    if remat:
        layer = jax.checkpoint(_layer, static_argnums=(2, 3, 4))
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        x = layer(x, params[f"layer_{i}"], cfg, kind, precision)
    return _rms(x, params["norm_f"]["scale"], cfg["layer_norm_epsilon"])


def logits(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, vocab] float32 logits."""
    return matmul(hidden(params, tokens, cfg, precision, remat),
                  params["lm_head"]["kernel"], precision)


def loss(params, batch, cfg, precision="highest"):
    """Mean next-token cross entropy of a block of rows (rows are
    independent, so the mean over blocks is the batch's loss). The head and
    the loss are computed ``HEAD_ROWS`` positions at a time, each recomputed
    in the backward."""
    tokens, labels = batch
    x = hidden(params, tokens, cfg, precision, remat=True)
    B, T, _ = x.shape
    rows = min(HEAD_ROWS, T)
    head = params["lm_head"]["kernel"]

    @jax.checkpoint
    def picked(x_rows, labels_rows):
        logp = jax.nn.log_softmax(matmul(x_rows, head, precision), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, labels_rows[..., None],
                                           axis=-1))

    total = sum(picked(x[:, i:i + rows], labels[:, i:i + rows])
                for i in range(0, T, rows))
    return -total / (B * T)


def block_rows(cfg, per_chip_batch):
    """Rows the loss may be computed on at a time: one sequence, whose
    scratch (a layer's float32 activations, a segment of the recurrence's
    states, a block of scores) lies beside four float32 trees of 667 M."""
    return 1
