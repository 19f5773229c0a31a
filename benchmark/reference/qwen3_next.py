"""Plain float32 reference of the Qwen3-Next-style hybrid decoder: no kernel, no
chunked rule, no sort, no cache. It imports nothing of the program.

As the published ``config.json`` and the family's reference code give the
layers (``h`` is a layer's input, eps 1e-6 everywhere):

- norm: zero-centred RMSNorm, ``x * rsqrt(mean(x^2) + eps) * (1 + w)``;
- layer ``i``: ``x += mixer(norm(x))``, ``x += moe(norm(x))``; the mixer is
  gated attention when ``(i + 1) % full_attention_interval == 0``, else
  Gated DeltaNet;
- gated attention: ``q_proj`` gives 16 heads of (query 256 | gate 256),
  ``k_proj``/``v_proj`` 2 heads of 256; q and k normed per head; rotary on
  the first 64 dims (half-rotation, theta 1e7); causal softmax at scale
  1/16, key/value head ``j`` serving query heads ``8j .. 8j+7``; the result
  times ``sigmoid(gate)``; ``o_proj``;
- Gated DeltaNet: ``in_proj_qkvz`` gives q, k (16 heads of 128), v, z (32 of
  128), ``in_proj_ba`` gives b, a; q, k, v through a causal depthwise
  convolution of 4 taps and SiLU; ``beta = sigmoid(b)``, ``g = -exp(A_log) *
  softplus(a + dt_bias)``; q, k L2-normalised per head, repeated to 32
  heads, q scaled by 1/sqrt(128); per head, with state ``S`` from zero,
  token by token: ``S = exp(g) S``; ``d = beta (v - S^T k)``; ``S += k d^T``;
  ``o = S^T q``; then ``o * rsqrt(mean(o^2) + eps) * w * silu(z)`` and
  ``out_proj``;
- sparse feed-forward: ``p = softmax(router(x))`` over all experts, the k
  largest, weights divided by their sum; the sum over the chosen experts
  that are HELD (``first_expert_held .. + num_experts``: the chip's share,
  as the configuration file states) of ``w * down(silu(gate(x)) * up(x))``,
  a dense loop over the held experts with masks; plus ``sigmoid(shared_gate
  (x)) * shared(x)``;
- untied head over the vocabulary slice, mean cross entropy.

Departures from a literal transcription, none of which changes a value: the
recurrence runs under a scan with a checkpoint every ``SEGMENT`` tokens (8192
states of 32 x 128 x 128 f32 are 17 GB a layer otherwise), the DeltaNet mixer
is computed ``GROUPS`` groups of heads at a time, attention in blocks of
``ROWS`` query rows, the experts under a scan with a checkpoint each, the
head and the loss ``HEAD_ROWS`` positions at a time, and each layer is
recomputed in the backward. The sizes are set by what the chip holds beside
the harness's own state at the second checked step (float32 parameters, two
moments and two gradients: 12.5 GB of 16.9): the program's scratch has to
stay under 1.9 GB. Every product
goes through ``precision.matmul`` / ``precision.operand`` so that the int8
control rounds both operands of all of them, the recurrence's included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .precision import HIGHEST, matmul, operand

SEGMENT = 64   # tokens between two checkpoints of the recurrence
ROWS = 128     # query rows of attention computed at a time
HEAD_ROWS = 1024  # positions of the head and the loss computed at a time
GROUPS = 4     # groups of heads the DeltaNet mixer is computed in


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=HIGHEST)


def _rms(x, w, eps, zero_centered=True):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def _rotary(x, rotary_dim, theta):
    """Half-rotation form on the first ``rotary_dim`` dims; x: [B, T, H, D]."""
    T = x.shape[1]
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2,
                                           dtype=jnp.float32) / rotary_dim))
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)          # [T, rotary_dim]
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rotated = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * cos + rotated * sin, rest], axis=-1)


def _attention(h, p, cfg, precision):
    B, T, _ = h.shape
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = matmul(h, p["q_proj"]["kernel"], precision).reshape(B, T, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:].reshape(B, T, H * D)
    k = matmul(h, p["k_proj"]["kernel"], precision).reshape(B, T, KV, D)
    v = matmul(h, p["v_proj"]["kernel"], precision).reshape(B, T, KV, D)
    q = _rms(q, p["q_norm"]["scale"], eps)
    k = _rms(k, p["k_norm"]["scale"], eps)
    rot = int(D * cfg["partial_rotary_factor"])
    q = _rotary(q, rot, float(cfg["rope_theta"]))
    k = _rotary(k, rot, float(cfg["rope_theta"]))
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
        s = jnp.where(mask, s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1)
        return start + rows, _einsum("bjgqk,bkjd->bqjgd", probs, v,
                                     precision)

    blocks = jnp.moveaxis(q.reshape(B, T // rows, rows, KV, group, D), 1, 0)
    _, out = jax.lax.scan(block, 0, blocks)
    a = jnp.moveaxis(out, 0, 1).reshape(B, T, H * D)
    a = a * jax.nn.sigmoid(gate)
    return matmul(a, p["o_proj"]["kernel"], precision)


def _delta_rule(q, k, v, g, beta, precision):
    """Token by token; q, k: [B, T, H, dk], v: [B, T, H, dv], g, beta:
    [B, T, H]. Returns o: [B, T, H, dv]."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    seg = min(SEGMENT, T)

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None, None]
        d_t = b_t[..., None] * (
            v_t - _einsum("bhkv,bhk->bhv", S, k_t, precision))
        S = S + _einsum("bhk,bhv->bhkv", k_t, d_t, precision)
        return S, _einsum("bhkv,bhk->bhv", S, q_t, precision)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    # time leads, in segments: [T / seg, seg, B, H, ...]
    lead = lambda x: jnp.moveaxis(x, 1, 0).reshape(
        (T // seg, seg) + x.shape[:1] + x.shape[2:])
    S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    _, o = jax.lax.scan(segment, S0, tuple(lead(x)
                                           for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((T, B, H, dv)), 0, 1)


def _delta_net(h, p, cfg, precision):
    """The mixer, ``GROUPS`` groups of heads at a time: the heads do not meet
    before ``out_proj``, whose rows a group's output multiplies, so the sum
    over groups is the layer. (One group is the literal transcription; more
    keep a group's q, k, v, z and states, not the layer's, alive at once.)"""
    B, T, d = h.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    eps = cfg["rms_norm_eps"]
    G = GROUPS if Hk % GROUPS == 0 else 1
    hk, hv = Hk // G, Hv // G
    nq, nv = Hk * dk, Hv * dv
    # columns [q | k | v | z] and [b | a], heads contiguous inside each:
    # a group's columns of every part, stacked with the group leading
    cols = lambda w, lo, n: jnp.moveaxis(
        w[..., lo:lo + n].reshape(w.shape[:-1] + (G, n // G)), -2, 0)
    w_in, w_ba = p["in_proj_qkvz"]["kernel"], p["in_proj_ba"]["kernel"]
    conv = p["conv"]["kernel"]                               # [taps, C]
    taps = conv.shape[0]
    parts = {
        "wq": cols(w_in, 0, nq), "wk": cols(w_in, nq, nq),
        "wv": cols(w_in, 2 * nq, nv), "wz": cols(w_in, 2 * nq + nv, nv),
        "wb": cols(w_ba, 0, Hv), "wa": cols(w_ba, Hv, Hv),
        "cq": cols(conv, 0, nq), "ck": cols(conv, nq, nq),
        "cv": cols(conv, 2 * nq, nv),
        "A_log": p["A_log"].reshape(G, hv),
        "dt_bias": p["dt_bias"].reshape(G, hv),
        "wo": p["out_proj"]["kernel"].reshape(G, hv * dv, d),
    }

    def conv_silu(x, w):   # y_t = sum_j w[j] x_{t - (taps - 1) + j}
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        y = 0.0
        for j in range(taps):
            y = y + operand(padded[:, j:j + T], precision) * operand(
                w[j], precision)
        return jax.nn.silu(y)

    l2 = lambda x: x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + eps)

    @jax.checkpoint
    def group(y, g_):
        q = conv_silu(matmul(h, g_["wq"], precision), g_["cq"])
        k = conv_silu(matmul(h, g_["wk"], precision), g_["ck"])
        v = conv_silu(matmul(h, g_["wv"], precision), g_["cv"])
        z = matmul(h, g_["wz"], precision)
        beta = jax.nn.sigmoid(matmul(h, g_["wb"], precision))
        decay = -jnp.exp(g_["A_log"]) * jax.nn.softplus(
            matmul(h, g_["wa"], precision) + g_["dt_bias"])
        q = l2(q.reshape(B, T, hk, dk)) / jnp.sqrt(jnp.float32(dk))
        k = l2(k.reshape(B, T, hk, dk))
        q = jnp.repeat(q, hv // hk, axis=2)
        k = jnp.repeat(k, hv // hk, axis=2)
        o = _delta_rule(q, k, v.reshape(B, T, hv, dv), decay, beta,
                        precision)
        o = _rms(o, p["norm"]["scale"], eps, zero_centered=False)
        o = o * jax.nn.silu(z.reshape(B, T, hv, dv))
        return y + matmul(o.reshape(B, T, hv * dv), g_["wo"], precision), None

    y, _ = jax.lax.scan(group, jnp.zeros_like(h), parts)
    return y


def _moe(h, p, cfg, precision):
    B, T, d = h.shape
    x = h.reshape(B * T, d)
    top_k = cfg["num_experts_per_tok"]
    first = cfg.get("first_expert_held", 0)
    # the router is float32 at full precision in every precision: which
    # experts a token goes to is not a product to be rounded
    logits = jnp.matmul(x, p["router"]["kernel"], precision=HIGHEST)
    weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

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
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        ex["gate"], ex["up"], ex["down"],
        first + jnp.arange(held, dtype=ids.dtype)))
    u = jax.nn.silu(matmul(x, p["shared_gate_proj"]["kernel"], precision)) \
        * matmul(x, p["shared_up_proj"]["kernel"], precision)
    shared = matmul(u, p["shared_down_proj"]["kernel"], precision)
    gate = jax.nn.sigmoid(matmul(x, p["shared_gate"]["kernel"], precision))
    return (y + gate * shared).reshape(B, T, d)


def _layer(x, p, cfg, attention, precision):
    eps = cfg["rms_norm_eps"]
    h = _rms(x, p["input_norm"]["scale"], eps)
    if attention:
        x = x + _attention(h, p["self_attn"], cfg, precision)
    else:
        x = x + _delta_net(h, p["linear_attn"], cfg, precision)
    h = _rms(x, p["post_norm"]["scale"], eps)
    return x + _moe(h, p["mlp"], cfg, precision)


def hidden(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, hidden] after the final norm."""
    x = params["embed_tokens"]["embedding"][tokens]
    layer = _layer
    if remat:
        layer = jax.checkpoint(_layer, static_argnums=(2, 3, 4))
    for i in range(cfg["num_hidden_layers"]):
        attention = (i + 1) % cfg["full_attention_interval"] == 0
        x = layer(x, params[f"layer_{i}"], cfg, attention, precision)
    return _rms(x, params["norm"]["scale"], cfg["rms_norm_eps"])


def logits(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, vocab] float32 logits."""
    return matmul(hidden(params, tokens, cfg, precision, remat),
                  params["lm_head"]["kernel"], precision)


def loss(params, batch, cfg, precision="highest"):
    """Mean next-token cross entropy of a block of rows (rows are
    independent, so the mean over blocks is the batch's loss). The head and
    the loss are computed ``ROWS`` positions at a time, each recomputed in
    the backward: the logits of 8192 positions and their gradient do not
    have to lie beside the parameters, moments and gradient at once."""
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
    """Rows the loss may be computed on at a time."""
    return 1
