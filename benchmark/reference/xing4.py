"""Plain float32 reference of the Xing4.0-style decoder: no kernel, no sort, no
cache. It imports nothing of the program.

As the published ``config.json`` names the mechanisms (``model_type``
``xing4_0``); C = ``hidden_size``, n = ``hc_mult`` residual streams, a
layer's input ``X`` is ``[n, C]`` a token, ``rms(x, w) = w * x *
rsqrt(mean(x^2) + rms_norm_eps)``:

- the embedding is copied into the n streams; a layer is two sublayers, the
  attention (``F = attn(rms(.))``) and the feed-forward (``F = ffn(rms(.))``),
  each inside one STREAM MIX (mHC: Manifold-Constrained Hyper-Connections,
  DeepSeek-AI 2025)::

      x~   = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)      # [nC], no gain
      H~   = alpha * (x~ @ phi) + b     # phi [nC, n + n + n*n]; alpha: three
                                        # scalars (pre, post, res); b [24]
      H_pre  = sigmoid(H~[:n]);  H_post = 2 * sigmoid(H~[n:2n])
      M      = exp(clip(H~[2n:].reshape(n, n), clamp_min, clamp_max))
      hc_sinkhorn_iters times:  M = M / (M.sum(-1, keepdims) + hc_eps)  # rows
                                M = M / (M.sum(-2, keepdims) + hc_eps)  # cols
      y      = F(sum_i H_pre[i] * X[i])
      X'[i]  = sum_j M[i, j] * X[j] + H_post[i] * y

  ``vec`` is stream-major; the n streams are summed in front of the final
  norm;
- latent attention (DeepSeek-V2's MLA): ``c_q = rms(x W_qa)``, ``q = c_q
  W_qb``, heads of ``[nope | rope]``; ``[c_kv | k_rope] = x W_kva``, ``c_kv =
  rms(c_kv)``, ``[k_nope | v]`` a head ``= c_kv W_kvb``; ``q_rope`` and the
  ONE ``k_rope`` head take rotary positions (half-rotation form, YaRN
  frequencies: ``inv_freq / factor * (1 - m) + inv_freq * m``, ``m`` 1 for a
  dimension that makes more than ``beta_fast`` rotations in
  ``original_max_position_embeddings`` positions, 0 for one that makes fewer
  than ``beta_slow``, linear in the index between; cos and sin times
  ``mscale / mscale_all_dim``); ``k = [k_nope | k_rope]``; causal softmax of
  ``q k^T * s``, ``s = (nope + rope)^-0.5 * (0.1 mscale_all_dim ln(factor) +
  1)^2``; ``(P v) W_o``;
- dense feed-forward (the first ``first_k_dense_replace`` layers) and the
  shared expert: ``w2(silu(w1 x) * w3 x)``;
- sparse feed-forward: ``s = sigmoid(router(x))`` over all
  ``n_routed_experts_total`` experts; ``ids = top_k(s + expert_bias)``
  (``n_group`` 1: the group step of ``noaux_tc`` is the identity); ``g =
  s[ids]``; ``g = g / (sum(g) + route_norm_eps)``; ``g =
  routed_scaling_factor * g``; the sum over the chosen experts that are HELD
  (``first_expert_held .. + n_routed_experts``: the chip's share, as the
  configuration file states) of ``g * down(silu(gate(x)) * up(x))``, a dense
  loop over the held experts with masks; plus the shared expert with no
  gate. The bias enters the choice only, so its gradient is exactly zero;
- untied head over the vocabulary slice; mean cross entropy.

Departures from a literal transcription, none of which changes a value:
attention runs ``HEAD_GROUPS`` groups of heads at a time (a group's q, k and
v are made from the two latents inside the group's checkpoint) and in blocks
of ``ROWS`` query rows, ``x~ @ phi`` is summed a stream's rows of ``phi`` at a
time with the token's scalar taken out, the experts under a scan with a
checkpoint each, the stream mixes and the feed-forwards (a mix and its
feed-forward are per token; the streams are held in chunks of positions, the
chunk first), the head and the loss ``HEAD_ROWS`` positions at a time, and
the layers are recomputed
in the backward ``LAYER_SPAN`` at a time, inside a span each layer, and
inside it each of its two mixed sublayers, so that the scratch of one
8192-token sequence fits the chip beside the harness's four float32 trees (a
float32 copy of the streams is 470 MB at 8192 tokens). Whatever runs a
chunk, a group or a block at a time runs under ``lax.map`` or ``lax.scan``:
unrolled, the compiler is free to run them side by side and hold them all.
Every bfloat16 product of the configuration goes through
``precision.matmul`` / ``precision.operand`` so that the int8 control rounds
both operands of all of them, the streams' weighted sums included; the
router's product and the mixing maps' (``x~ @ phi``, the Sinkhorn rounds)
stay float32 at full precision in every precision, as the configuration
states them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .precision import HIGHEST, matmul, operand

ROWS = 128        # query rows of attention computed at a time
HEAD_GROUPS = 4   # groups of heads attention is computed in
HEAD_ROWS = 1024  # positions of the feed-forwards, the head and the loss
LAYER_SPAN = 2    # layers recomputed together in the backward


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=HIGHEST)


def _rms(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps))


def yarn_inv_freq(cfg):
    """``[qk_rope_head_dim / 2]`` rotary frequencies after YaRN."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    r = cfg["rope_scaling"]

    def index_of(rotations):
        return dim * math.log(r["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (
                                  2 * math.log(base))

    low = max(math.floor(index_of(r["beta_fast"])), 0)
    high = min(math.ceil(index_of(r["beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    m = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = 1.0 / (base ** (2.0 * i / dim))
    return inv_freq / r["factor"] * (1.0 - m) + inv_freq * m


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg):
    r = cfg["rope_scaling"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return width ** -0.5 * _mscale(r["factor"], r["mscale_all_dim"]) ** 2


def _rotary(x, cfg):
    """Half-rotation form over the whole of ``x``'s last axis (the rope
    part); x: [B, T, H, D]."""
    T, D = x.shape[1], x.shape[-1]
    r = cfg["rope_scaling"]
    scale = _mscale(r["factor"], r["mscale"]) / _mscale(
        r["factor"], r["mscale_all_dim"])
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)
    emb = jnp.concatenate([freqs, freqs], axis=-1)          # [T, D]
    cos = scale * jnp.cos(emb)[None, :, None, :]
    sin = scale * jnp.sin(emb)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rotated * sin


def _attention(h, p, cfg, precision):
    B, T, _ = h.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, lat = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    c_q = _rms(matmul(h, p["q_a_proj"]["kernel"], precision),
               p["q_a_layernorm"]["scale"], eps)
    kv_a = matmul(h, p["kv_a_proj"]["kernel"], precision)
    c_kv = _rms(kv_a[..., :lat], p["kv_a_layernorm"]["scale"], eps)
    k_rope = _rotary(kv_a[:, :, None, lat:], cfg)            # one head
    rows = min(ROWS, T)
    scale = softmax_scale(cfg)
    G = H // math.gcd(H, HEAD_GROUPS)                        # heads a group

    @jax.checkpoint
    def heads(w_qb, w_kvb, w_o):
        """``G`` heads from the two latents to their part of ``W_o``'s sum:
        their q, k and v are made here, so only a group's are alive."""
        q = matmul(c_q, w_qb, precision).reshape(B, T, G, dn + dr)
        kv = matmul(c_kv, w_kvb, precision).reshape(B, T, G, dn + dv)
        q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], cfg)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, G, dr))], axis=-1)
        v = kv[..., dn:]

        @jax.checkpoint
        def block(start, q_rows):
            s = _einsum("bqhd,bkhd->bhqk", q_rows, k, precision) * scale
            pos_q = start + jnp.arange(rows)
            mask = pos_q[:, None] >= jnp.arange(T)[None, :]
            probs = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return start + rows, _einsum("bhqk,bkhd->bqhd", probs, v,
                                         precision)

        blocks = jnp.moveaxis(q.reshape(B, T // rows, rows, G, dn + dr), 1, 0)
        _, out = jax.lax.scan(block, 0, blocks)
        a = jnp.moveaxis(out, 0, 1).reshape(B, T, G * dv)
        return matmul(a, w_o, precision)

    # one group after another (a scan: unrolled, the groups could be held
    # side by side), their parts of W_o's product summed
    groups = H // G
    by_group = lambda w, width: jnp.moveaxis(
        w.reshape(w.shape[0], groups, G * width), 1, 0)
    out, _ = jax.lax.scan(
        lambda total, w: (total + heads(*w), None),
        jnp.zeros(h.shape, h.dtype),
        (by_group(p["q_b_proj"]["kernel"], dn + dr),
         by_group(p["kv_b_proj"]["kernel"], dn + dv),
         p["o_proj"]["kernel"].reshape(groups, G * dv, -1)))
    return out


def _split(a):
    """``[B, T, ...] -> [T / rows, B, rows, ...]``: chunks of ``HEAD_ROWS``
    positions, the chunk first."""
    B, T = a.shape[:2]
    rows = min(HEAD_ROWS, T)
    return jnp.moveaxis(a.reshape((B, T // rows, rows) + a.shape[2:]), 1, 0)


def _join(a):
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape((a.shape[0], -1) + a.shape[3:])


def _chunks(f, *arrays):
    """``f`` over the leading (chunk) axis of ``arrays``, one chunk after
    another under ``lax.map`` (unrolled, the compiler is free to run the
    chunks side by side and hold them all), each recomputed in the
    backward."""
    return jax.lax.map(lambda xs: jax.checkpoint(f)(*xs), arrays)


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _swiglu(x, p, precision):
    u = jax.nn.silu(matmul(x, p["w1"]["kernel"], precision)) * matmul(
        x, p["w3"]["kernel"], precision)
    return matmul(u, p["w2"]["kernel"], precision)


def route(x, p, cfg):
    """``(weights [S, k], ids [S, k])`` of the tokens ``x`` (``[S, C]``).
    The router is float32 at full precision in every precision: which
    experts a token goes to is not a product to be rounded."""
    logits = jnp.matmul(x, p["router"]["kernel"], precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + p["expert_bias"],
                           cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + cfg["route_norm_eps"])
    return cfg["routed_scaling_factor"] * weights, ids


def routed(h, p, cfg, precision):
    """The held experts' part of the sparse layer's result."""
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


def mix_maps(X, p, cfg):
    """``(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n])`` of the
    streams ``X`` (``[n, B, T, C]``): float32 at full precision in every
    precision."""
    n, B, T, C = X.shape
    # x~ @ phi over the stream-major vec(X), a stream's rows of phi at a
    # time, with the token's scalar taken out of the product: no second
    # copy of the streams is made
    inv_rms = jax.lax.rsqrt(jnp.mean(X * X, axis=(0, 3))
                            + cfg["rms_norm_eps"])           # [B, T]
    phi = p["phi"].reshape(n, C, -1)
    product = sum(jnp.matmul(X[i], phi[i], precision=HIGHEST)
                  for i in range(n)) * inv_rms[..., None]
    alpha = jnp.concatenate([jnp.full((w,), 1.0) * p["alpha"][i]
                             for i, w in enumerate((n, n, n * n))])
    maps = alpha * product + p["b"]
    m = jnp.exp(jnp.clip(maps[..., 2 * n:], cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"])).reshape(B, T, n, n)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg["hc_eps"])
    return (jax.nn.sigmoid(maps[..., :n]),
            2.0 * jax.nn.sigmoid(maps[..., n:2 * n]), m)


def _mix_read(X, p, cfg, precision):
    """``(h, post, res)``: the sublayer's input and the maps its write
    needs."""
    pre, post, res = mix_maps(X, p, cfg)
    Xo = operand(X, precision)
    # the weighted sum a stream at a time (elementwise, float32)
    return sum(pre[..., i, None] * Xo[i] for i in range(X.shape[0])), post, res


def _mix_write(X, y, post, res, precision):
    n = X.shape[0]
    Xo, y = operand(X, precision), operand(y, precision)
    return jnp.stack([
        sum(res[..., i, j, None] * Xo[j] for j in range(n))
        + post[..., i, None] * y for i in range(n)])


def _layer(X, p, cfg, dense, precision):
    """``X``: the streams in chunks of ``HEAD_ROWS`` positions, ``[T / rows,
    n, B, rows, C]``. A stream mix is per token, so everything but the
    attention itself runs a chunk at a time and no copy of the whole streams
    is made: a float32 copy is 470 MB at 8192 tokens, and a mix's backward
    would hold some eight."""
    eps = cfg["rms_norm_eps"]

    def attn(X):
        h, post, res = _chunks(
            lambda X: _mix_read(X, p["attn_hc"], cfg, precision), X)
        y = _attention(_rms(_join(h), p["input_layernorm"]["scale"], eps),
                       p["self_attn"], cfg, precision)
        return _chunks(
            lambda X, y, post, res: _mix_write(X, y, post, res, precision),
            X, _split(y), post, res)

    def ffn(X):
        def F(h):
            h = _rms(h, p["post_attention_layernorm"]["scale"], eps)
            if dense:
                return _swiglu(h, p["mlp"], precision)
            return routed(h, p["mlp"], cfg, precision) + _swiglu(
                h, p["shared_expert"], precision)

        def rows(X):
            h, post, res = _mix_read(X, p["ffn_hc"], cfg, precision)
            return _mix_write(X, F(h), post, res, precision)

        return _chunks(rows, X)

    return jax.checkpoint(ffn)(jax.checkpoint(attn)(X))


def hidden(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> the final norm's output in chunks of positions,
    ``[T / rows, B, rows, hidden]``. With ``remat`` the layers are
    recomputed in the backward ``LAYER_SPAN`` at a time, and inside a span
    each layer again: one span's input is saved where each layer's would be
    (a layer's input is 470 MB of float32 at 8192 tokens)."""
    x = _split(params["embed_tokens"]["embedding"][tokens])
    n_dense, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    layer = jax.checkpoint(_layer, static_argnums=(2, 3, 4)) if remat \
        else _layer

    def span(carry, layers, first):
        # the copy into the streams is the first span's own
        X = carry if first else jnp.broadcast_to(
            carry[:, None], carry.shape[:1] + (cfg["hc_mult"],)
            + carry.shape[1:])
        for j, p in enumerate(layers):
            X = layer(X, p, cfg, first + j < n_dense, precision)
        return X

    run = jax.checkpoint(span, static_argnums=(2,)) if remat else span
    carry = x
    for first in range(0, L, LAYER_SPAN):
        carry = run(carry, [params[f"layer_{i}"] for i in
                            range(first, min(first + LAYER_SPAN, L))], first)
    return _rms(jnp.sum(carry, axis=1), params["norm"]["scale"],
                cfg["rms_norm_eps"])


def logits(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, vocab] float32 logits (untied head)."""
    return matmul(_join(hidden(params, tokens, cfg, precision, remat)),
                  params["lm_head"]["kernel"], precision)


def loss(params, batch, cfg, precision="highest"):
    """Mean next-token cross entropy of a block of rows (rows are
    independent, so the mean over blocks is the batch's loss). The head and
    the loss are computed ``HEAD_ROWS`` positions at a time, each recomputed
    in the backward."""
    tokens, labels = batch
    x = hidden(params, tokens, cfg, precision, remat=True)
    head = params["lm_head"]["kernel"]

    @jax.checkpoint
    def picked(x_rows, labels_rows):
        logp = jax.nn.log_softmax(matmul(x_rows, head, precision), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, labels_rows[..., None],
                                           axis=-1))

    # (a plain loop here: under lax.map the head's gradient is summed in a
    # second copy of it)
    labels = _split(labels)
    return -sum(picked(x[i], labels[i])
                for i in range(x.shape[0])) / tokens.size


def block_rows(cfg, per_chip_batch):
    """Rows the loss may be computed on at a time: one sequence."""
    return 1
