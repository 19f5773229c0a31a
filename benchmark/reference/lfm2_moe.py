"""Plain float32 reference of the LFM2-MoE-style hybrid decoder: no kernel, no
sort, no cache. It imports nothing of the program.

As the published ``config.json`` and the family's reference code give the
layers (``n(x) = w * x * rsqrt(mean(x^2) + norm_eps)``, ``w`` from ones):

- layer ``i``: ``h = x + mixer(n_op(x))``, ``y = h + ffn(n_ffn(h))``; the
  mixer is what ``layer_types[i]`` says; the feed-forward is dense in the
  first ``num_dense_layers`` layers and sparse in the others;
- ``conv``: ``[B | C | u] = in_proj(x)`` (three equal parts), ``s_t = sum_j
  k[j] * (B * u)_{t - (L - 1) + j}`` per channel with zeros before the
  sequence (depthwise, causal, no bias, no activation), ``out_proj(C * s)``;
- ``full_attention``: ``q_proj`` gives ``num_attention_heads`` heads,
  ``k_proj``/``v_proj`` ``num_key_value_heads``, of ``hidden_size /
  num_attention_heads``; q and k normed per head; rotary positions over the
  whole head (half-rotation form, ``rope_theta``); causal softmax at scale
  ``head ** -0.5``, key/value head ``j`` serving query heads ``g j .. g j +
  g - 1``; ``out_proj``;
- dense feed-forward: ``w2(silu(w1 x) * w3 x)``;
- sparse feed-forward: ``s = sigmoid(router(x))`` over all experts; ``ids =
  top_k(s + expert_bias)``; ``g = s[ids]``; ``g = g / (sum(g) + 1e-6)``;
  ``g = routed_scaling_factor * g``; the sum over the chosen experts that
  are HELD (``first_expert_held .. + num_experts``: the chip's share, as the
  configuration file states) of ``g * down(silu(gate(x)) * up(x))``, a dense
  loop over the held experts with masks. No shared expert. The bias enters
  the choice only, so its gradient is exactly zero;
- the head is the embedding's transpose over the vocabulary slice; mean
  cross entropy.

Departures from a literal transcription, none of which changes a value:
attention runs in blocks of ``ROWS`` query rows, the experts under a scan
with a checkpoint each, the dense feed-forward, the head and the loss
``HEAD_ROWS`` positions at a time, and each layer is recomputed in the
backward, so that a block of two rows fits the chip beside the harness's
own state (``block_rows``). Every product goes through ``precision.matmul`` /
``precision.operand`` so that the int8 control rounds both operands of all
of them, the gates and the taps included; the router's product stays
float32 at full precision in every precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .precision import HIGHEST, matmul, operand

ROWS = 128        # query rows of attention computed at a time
HEAD_ROWS = 1024  # positions of the dense feed-forward, the head and the loss
NORM_EPS = 1e-6   # under the chosen weights' sum, as published


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=HIGHEST)


def _rms(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps))


def _rotary(x, theta):
    """Half-rotation form over the whole head; x: [B, T, H, D]."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)          # [T, D]
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rotated * sin


def _short_conv(h, p, precision):
    T = h.shape[1]
    gate_in, gate_out, u = jnp.split(
        matmul(h, p["in_proj"]["kernel"], precision), 3, axis=-1)
    bu = operand(gate_in, precision) * operand(u, precision)
    kernel = p["conv"]["kernel"]                             # [taps, C]
    taps = kernel.shape[0]
    padded = jnp.pad(bu, ((0, 0), (taps - 1, 0), (0, 0)))
    s = 0.0
    for j in range(taps):   # s_t = sum_j kernel[j] * bu_{t - (taps - 1) + j}
        s = s + operand(padded[:, j:j + T], precision) * operand(
            kernel[j], precision)
    y = operand(gate_out, precision) * operand(s, precision)
    return matmul(y, p["out_proj"]["kernel"], precision)


def _attention(h, p, cfg, precision):
    B, T, d = h.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = d // H
    eps = cfg["norm_eps"]
    q = matmul(h, p["q_proj"]["kernel"], precision).reshape(B, T, H, D)
    k = matmul(h, p["k_proj"]["kernel"], precision).reshape(B, T, KV, D)
    v = matmul(h, p["v_proj"]["kernel"], precision).reshape(B, T, KV, D)
    q = _rotary(_rms(q, p["q_layernorm"]["scale"], eps),
                float(cfg["rope_theta"]))
    k = _rotary(_rms(k, p["k_layernorm"]["scale"], eps),
                float(cfg["rope_theta"]))
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


def _in_rows(f, x, rows):
    """``f`` over the positions of ``x`` (``[B, T, ...]``) ``rows`` at a
    time, each block recomputed in the backward."""
    T = x.shape[1]
    rows = min(rows, T)
    f = jax.checkpoint(f)
    return jnp.concatenate([f(x[:, i:i + rows]) for i in range(0, T, rows)],
                           axis=1)


def _dense_ffn(h, p, precision):
    def rows(x):
        u = jax.nn.silu(matmul(x, p["w1"]["kernel"], precision)) * matmul(
            x, p["w3"]["kernel"], precision)
        return matmul(u, p["w2"]["kernel"], precision)

    return _in_rows(rows, h, HEAD_ROWS)


def route(x, p, cfg):
    """``(weights [S, k], ids [S, k])`` of the tokens ``x`` (``[S, d]``).
    The router is float32 at full precision in every precision: which
    experts a token goes to is not a product to be rounded."""
    logits = jnp.matmul(x, p["router"]["kernel"], precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choose = scores + p["expert_bias"] if cfg["use_expert_bias"] else scores
    _, ids = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + NORM_EPS)
    return cfg["routed_scaling_factor"] * weights, ids


def _moe(h, p, cfg, precision):
    B, T, d = h.shape
    x = h.reshape(B * T, d)
    weights, ids = route(x, p, cfg)

    @jax.checkpoint
    def expert(y, e):
        w_gate, w_up, w_down, index = e
        # this expert's weight for every token: zero where it was not chosen
        mine = jnp.sum(jnp.where(ids == index, weights, 0.0), axis=-1)
        u = jax.nn.silu(matmul(x, w_gate, precision)) * matmul(
            x, w_up, precision)
        return y + mine[:, None] * matmul(u, w_down, precision), None

    ex = p["experts"]
    held = ex["gate"].shape[0]
    first = cfg.get("first_expert_held", 0)
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        ex["gate"], ex["up"], ex["down"],
        first + jnp.arange(held, dtype=ids.dtype)))
    return y.reshape(B, T, d)


def _layer(x, p, cfg, kind, dense, precision):
    eps = cfg["norm_eps"]
    h = _rms(x, p["operator_norm"]["scale"], eps)
    if kind == "full_attention":
        x = x + _attention(h, p["self_attn"], cfg, precision)
    elif kind == "conv":
        x = x + _short_conv(h, p["conv"], precision)
    else:
        raise ValueError(f"no layer kind {kind!r}")
    h = _rms(x, p["ffn_norm"]["scale"], eps)
    if dense:
        return x + _dense_ffn(h, p["feed_forward"], precision)
    return x + _moe(h, p["feed_forward"], cfg, precision)


def hidden(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, hidden] after the final norm."""
    x = params["embed_tokens"]["embedding"][tokens]
    layer = _layer
    if remat:
        layer = jax.checkpoint(_layer, static_argnums=(2, 3, 4, 5))
    for i, kind in enumerate(cfg["layer_types"]):
        x = layer(x, params[f"layer_{i}"], cfg, kind,
                  i < cfg["num_dense_layers"], precision)
    return _rms(x, params["norm"]["scale"], cfg["norm_eps"])


def logits(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, vocab] float32 logits: the head is the
    embedding's transpose."""
    return matmul(hidden(params, tokens, cfg, precision, remat),
                  params["embed_tokens"]["embedding"].T, precision)


def loss(params, batch, cfg, precision="highest"):
    """Mean next-token cross entropy of a block of rows (rows are
    independent, so the mean over blocks is the batch's loss). The head and
    the loss are computed ``HEAD_ROWS`` positions at a time, each recomputed
    in the backward."""
    tokens, labels = batch
    x = hidden(params, tokens, cfg, precision, remat=True)
    B, T, _ = x.shape
    rows = min(HEAD_ROWS, T)
    head = params["embed_tokens"]["embedding"].T

    @jax.checkpoint
    def picked(x_rows, labels_rows):
        logp = jax.nn.log_softmax(matmul(x_rows, head, precision), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, labels_rows[..., None],
                                           axis=-1))

    total = sum(picked(x[:, i:i + rows], labels[:, i:i + rows])
                for i in range(0, T, rows))
    return -total / (B * T)


def block_rows(cfg, per_chip_batch):
    """Rows the loss may be computed on at a time. Two, sized by PR 32 for a
    harness that held six float32 trees of 507.8 M at its later steps
    (parameters, two moments, the sum, a stale gradient and the new one: 12.2
    GB) beside 2.7 GB of scratch (3.0 GB in the int8 control). Since PR 40
    ``check_train.reference_steps`` holds five at a step's second block
    (parameters, two moments, the sum and the new gradient: 10.2 GB) and four
    at its first; the two rows stay, because the numbers the limits were set
    from were read with them (another blocking sums a step's gradient in
    another order)."""
    return min(2, per_chip_batch)
