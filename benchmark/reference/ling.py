"""Plain float32 reference of the Ling-3.0-style hybrid decoder: no kernel, no
chunked rule, no sort, no cache. It imports nothing of the program.

As the published ``config.json`` and the public Kimi-delta-attention code give
the layers (``n(x) = w * x * rsqrt(mean(x^2) + rms_norm_eps)``, ``w`` from
ones). Layer ``i`` is ``x += mixer(n(x)); x += ffn(n(x))``; its mixer is what
entry ``i`` of ``layer_mixers`` says:

- ``kda``, Kimi delta attention (``H = num_attention_heads`` heads of ``d =
  head_dim``): ``q~, k~, v~ = q_proj x, k_proj x, v_proj x``, each through a
  causal depthwise convolution of ``short_conv_kernel_size`` taps without
  bias (zeros before the sequence), then SiLU; per head ``q = q~ / sqrt(|q~|^2
  + eps) * d^-1/2``, ``k = k~ / sqrt(|k~|^2 + eps)``, ``v = v~``; ``beta =
  sigmoid(b_proj x)``, one a head; the gate, ``d`` numbers a head and token,
  ``g = kda_lower_bound * sigmoid(exp(A_log_h) * (f_proj x + dt_bias))``; per
  head, token by token from a zero state ``S [d, d]``: ``S = diag(exp(g)) S``,
  ``e = beta (v - S^T k)``, ``S = S + k e^T``, ``o = S^T q``; ``y =
  o_proj(n_head(o) * sigmoid(g_proj x))``, the norm over each head's ``d``
  with one weight vector;
- ``mla``, latent attention: ``q = q_proj x`` as ``H`` heads of ``qk_nope +
  qk_rope``; ``[c | k_r] = kv_a_proj x``, ``c = n(c)``, ``[k_n | v] =
  kv_b_proj c`` a head; rotary positions on neighbouring pairs (``2 i`` with
  ``2 i + 1``) at ``rope_theta`` on the rope part of every query head and on
  ``k_r``, the one rotary key head that all heads share; causal softmax at
  scale ``(qk_nope + qk_rope)^-1/2``; ``y = o_proj(a_h * sigmoid(g_proj
  x)_h)``, one gate a head;
- the feed-forward of the first ``first_k_dense_replace`` layers is SwiGLU;
  behind them ``s = sigmoid(router x)`` over all experts in float32; on ``s +
  expert_bias`` the experts' ``n_group`` groups are scored by the sum of their
  two best, the best ``topk_group`` groups kept, the best
  ``num_experts_per_tok`` experts chosen among theirs; ``w = s[ids]``, ``w =
  routed_scaling_factor * w / (sum(w) + 1e-20)``; the sum over the chosen
  experts that are HELD (``first_expert_held .. + num_experts``: the chip's
  share, as the configuration file states) of ``w * down(silu(gate u) * up
  u)``, a dense loop over the held experts with masks; plus the shared
  expert, unweighted. The bias enters the choice only, so its gradient is
  exactly zero;
- after the last layer ``norm`` and the untied head over the vocabulary
  slice; mean cross entropy.

Departures from a literal transcription, none of which changes a value: the
delta-attention mixer is computed ``KDA_HEADS`` heads at a time (heads do not
meet before ``o_proj``), its recurrence is checkpointed every ``SEGMENT``
tokens, attention runs in blocks of ``ROWS`` query rows over groups of heads,
the experts under a scan with a checkpoint each, the feed-forwards, the head
and the loss ``HEAD_ROWS`` positions at a time, and each layer is recomputed
in the backward, so that one sequence fits the chip beside the harness's own
state (``block_rows``). Whatever runs a group or a block at a time runs under
``lax.scan`` or ``lax.map``: unrolled, the compiler is free to hold them all.
Every product goes through ``precision.matmul`` / ``precision.operand`` so
that the int8 control rounds both operands of all of them, the taps and the
recurrence's included; the router's product stays float32 at full precision
in every precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .precision import HIGHEST, matmul, operand

KDA_HEADS = 8     # heads the delta-attention mixer is computed at a time
SEGMENT = 64      # tokens between two checkpoints of the recurrence
ROWS = 128        # query rows of attention computed at a time
HEAD_GROUPS = 4   # groups of heads attention is computed in
HEAD_ROWS = 1024  # positions of the feed-forwards, the head and the loss
ROUTE_NORM_EPS = 1e-20  # under the chosen weights' sum


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=HIGHEST)


def _rms(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps))


def delta_rule(q, k, v, g, beta, precision="highest"):
    """Token by token; ``q``, ``k``, ``g``: ``[b, T, H, dk]``, ``v``: ``[b,
    T, H, dv]``, ``beta``: ``[b, T, H]``. Returns ``o``: ``[b, T, H, dv]``."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    seg = SEGMENT if T % SEGMENT == 0 else T

    def token(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[..., None]
        e = b_t[..., None] * (v_t - _einsum("bhkv,bhk->bhv", S, k_t,
                                            precision))
        S = S + _einsum("bhk,bhv->bhkv", k_t, e, precision)
        return S, _einsum("bhkv,bhk->bhv", S, q_t, precision)

    @jax.checkpoint
    def segment(S, ts):
        return jax.lax.scan(token, S, ts)

    # time leads, in segments: [T / seg, seg, b, H, ...]
    lead = lambda m: jnp.moveaxis(m, 1, 0).reshape(
        (T // seg, seg) + m.shape[:1] + m.shape[2:])
    _, o = jax.lax.scan(segment, jnp.zeros((b, H, dk, dv), jnp.float32),
                        tuple(lead(m) for m in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((T, b, H, dv)), 0, 1)


def _kda(h, p, cfg, precision):
    """The mixer, ``KDA_HEADS`` heads at a time: heads do not meet before
    ``o_proj``, whose rows a head's output multiplies, so the sum over the
    groups of heads is the layer."""
    b, T, _ = h.shape
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    eps, taps = cfg["rms_norm_eps"], cfg["short_conv_kernel_size"]
    per = math.gcd(H, KDA_HEADS)
    groups = H // per
    # a group's columns of every matrix, the group leading
    cols = lambda w: jnp.moveaxis(
        w.reshape(w.shape[:-1] + (groups, per * d)), -2, 0)
    parts = {
        **{n: cols(p[f"{n}_proj"]["kernel"]) for n in "qkvfg"},
        **{n + "_taps": cols(p[f"{n}_conv"]["kernel"]) for n in "qkv"},
        "b": jnp.moveaxis(
            p["b_proj"]["kernel"].reshape(-1, groups, per), 1, 0),
        "dt_bias": p["dt_bias"].reshape(groups, per, d),
        "A_log": p["A_log"].reshape(groups, per),
        "o": p["o_proj"]["kernel"].reshape(groups, per * d, -1),
    }

    def conv_silu(x, w):  # s_t = sum_j w[j] x_{t - (taps - 1) + j}
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        s = 0.0
        for j in range(taps):
            s = s + operand(padded[:, j:j + T], precision) * operand(
                w[j], precision)
        return jax.nn.silu(s)

    def group(w):
        heads = lambda n: conv_silu(
            matmul(h, w[n], precision), w[n + "_taps"]).reshape(b, T, per, d)
        unit = lambda y: y * jax.lax.rsqrt(
            jnp.sum(y * y, axis=-1, keepdims=True) + eps)
        gate = cfg["kda_lower_bound"] * jax.nn.sigmoid(
            jnp.exp(w["A_log"])[:, None] * (
                matmul(h, w["f"], precision).reshape(b, T, per, d)
                + w["dt_bias"]))
        o = delta_rule(unit(heads("q")) * d ** -0.5, unit(heads("k")),
                       heads("v"), gate,
                       jax.nn.sigmoid(matmul(h, w["b"], precision)), precision)
        o = _rms(o, p["o_norm"]["scale"], eps) * jax.nn.sigmoid(
            matmul(h, w["g"], precision).reshape(b, T, per, d))
        return matmul(o.reshape(b, T, per * d), w["o"], precision)

    @jax.checkpoint
    def one(y, w):
        return y + group(w), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), parts)
    return y


def _rotary(x, cfg):
    """Neighbouring pairs rotated in place over the whole of ``x``'s last
    axis (the rope part); x: [B, T, H, D]."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = float(cfg["rope_theta"]) ** (
        -jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq   # [T, D/2]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(h, p, cfg, precision):
    B, T, _ = h.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, lat = cfg["v_head_dim"], cfg["kv_lora_rank"]
    kv_a = matmul(h, p["kv_a_proj"]["kernel"], precision)
    c_kv = _rms(kv_a[..., :lat], p["kv_a_layernorm"]["scale"],
                cfg["rms_norm_eps"])
    k_rope = _rotary(kv_a[:, :, None, lat:], cfg)            # one head
    rows = min(ROWS, T)
    scale = (dn + dr) ** -0.5
    G = H // math.gcd(H, HEAD_GROUPS)                        # heads a group

    @jax.checkpoint
    def heads(w_q, w_kvb, w_g, w_o):
        """``G`` heads from ``x`` and the latent to their part of ``o_proj``'s
        sum: their q, k and v are made here, so only a group's are alive."""
        q = matmul(h, w_q, precision).reshape(B, T, G, dn + dr)
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
        a = jnp.moveaxis(out, 0, 1).reshape(B, T, G, dv)
        # one gate a head on the attention's output
        a = a * jax.nn.sigmoid(matmul(h, w_g, precision))[..., None]
        return matmul(a.reshape(B, T, G * dv), w_o, precision)

    # one group after another (a scan: unrolled, the groups could be held
    # side by side), their parts of o_proj's product summed
    groups = H // G
    by_group = lambda w, width: jnp.moveaxis(
        w.reshape(w.shape[0], groups, G * width), 1, 0)
    out, _ = jax.lax.scan(
        lambda total, w: (total + heads(*w), None),
        jnp.zeros(h.shape, h.dtype),
        (by_group(p["q_proj"]["kernel"], dn + dr),
         by_group(p["kv_b_proj"]["kernel"], dn + dv),
         by_group(p["g_proj"]["kernel"], 1),
         p["o_proj"]["kernel"].reshape(groups, G * dv, -1)))
    return out


def _chunks(f, x):
    """``f`` over ``x`` (``[B, T, ...]``) ``HEAD_ROWS`` positions at a time
    under ``lax.map``, each chunk recomputed in the backward."""
    B, T = x.shape[:2]
    rows = min(HEAD_ROWS, T)
    split = jnp.moveaxis(x.reshape((B, T // rows, rows) + x.shape[2:]), 1, 0)
    y = jax.lax.map(jax.checkpoint(f), split)
    y = jnp.moveaxis(y, 0, 1)
    return y.reshape((B, T) + y.shape[3:])


def _swiglu(x, p, precision):
    u = jax.nn.silu(matmul(x, p["w1"]["kernel"], precision)) * matmul(
        x, p["w3"]["kernel"], precision)
    return matmul(u, p["w2"]["kernel"], precision)


def route(x, p, cfg):
    """``(weights [S, k], ids [S, k])`` of the tokens ``x`` (``[S, d]``).
    The router is float32 at full precision in every precision: which
    experts a token goes to is not a product to be rounded."""
    S = x.shape[0]
    n_group, kept = cfg["n_group"], cfg["topk_group"]
    logits = jnp.matmul(x, p["router"]["kernel"], precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    biased = (scores + p["expert_bias"]).reshape(S, n_group, -1)
    # a group's score: the sum of its two best; the best groups are kept
    group_score = jnp.sum(jax.lax.top_k(biased, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, kept)
    open_ = jnp.zeros((S, n_group), bool).at[
        jnp.arange(S)[:, None], best].set(True)
    choice = jnp.where(open_[..., None], biased, -jnp.inf).reshape(S, -1)
    _, ids = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + ROUTE_NORM_EPS)
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


def _layer(x, p, cfg, kind, dense, precision):
    eps = cfg["rms_norm_eps"]
    h = _rms(x, p["input_layernorm"]["scale"], eps)
    if kind == "mla":
        x = x + _attention(h, p["self_attn"], cfg, precision)
    elif kind == "kda":
        x = x + _kda(h, p["linear_attn"], cfg, precision)
    else:
        raise ValueError(f"no mixer {kind!r}")
    h = _rms(x, p["post_attention_layernorm"]["scale"], eps)
    if dense:
        return x + _chunks(lambda r: _swiglu(r, p["mlp"], precision), h)
    return (x + routed(h, p["mlp"], cfg, precision)
            + _chunks(lambda r: _swiglu(r, p["shared_expert"], precision), h))


def hidden(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, hidden] after the final norm."""
    x = params["embed_tokens"]["embedding"][tokens]
    layer = _layer
    if remat:
        layer = jax.checkpoint(_layer, static_argnums=(2, 3, 4, 5))
    for i, kind in enumerate(cfg["layer_mixers"]):
        x = layer(x, params[f"layer_{i}"], cfg, kind,
                  i < cfg["first_k_dense_replace"], precision)
    return _rms(x, params["norm"]["scale"], cfg["rms_norm_eps"])


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
    scratch (a layer's float32 activations, a group of heads' recurrence, a
    block of scores) lies beside four float32 trees of 884 M."""
    return 1
