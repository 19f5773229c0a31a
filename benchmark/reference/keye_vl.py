"""Plain float32 reference of the Keye-VL-2.0-style language model: no kernel,
no cache. It imports nothing of the program.

As the published ``config.json`` gives the sizes (``model_type`` ``KeyeVL2``)
and the family's report (DeepSeek-V3.2-Exp: the lightning indexer and its
sparse training stage) the indexer's form; every layer alike, ``rms(x, w) =
w * x * rsqrt(mean(x^2) + rms_norm_eps)``:

- layer: ``h = rms(x)``; ``x += attn(h)``; ``x += experts(rms(x))``;
- main attention: ``q = h W_q`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k``, ``v`` as ``num_key_value_heads`` heads, no bias; q and
  k normed per head (``rms``); rotary positions over the whole head in the
  half-rotation form (pairs ``(i, i + head_dim / 2)``), the ``head_dim / 2``
  frequencies divided ``mrope_section`` among the temporal, the height and
  the width position id (``positions`` is ``[3, B, T]``; text gives all three
  rows ``arange(T)``); scale ``head_dim ** -0.5``; query head ``j`` reads
  key/value head ``j // group``;
- indexer, from ``g = stop_gradient(h)``: ``qI = g W_qI`` as
  ``indexer_num_heads`` heads of ``indexer_head_dim``; ``kI = LayerNorm(g
  W_kI)`` (one head, weight and bias); rotary over the whole indexer head at
  the temporal id; ``w = (g W_wI) * heads ** -0.5 * head_dim ** -0.5``;
  ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])`` for ``s <= t``;
- selection: ``S_t`` = the ``min(t + 1, topk)`` positions ``s <= t`` of the
  largest ``I[t, s]``, ties toward the lower position: what
  ``jax.lax.top_k`` gives on a row with ``-inf`` beyond ``t``. The set is
  built from ``top_k``'s k-th VALUE (everything above it, and of the
  positions that equal it the lowest, as many as are still missing), which
  is the set of ``top_k``'s indices without a scatter of them (a test holds
  the two equal); no gradient passes through it;
- sparse attention: per head the softmax over ``S_t`` alone, times ``v``;
  ``W_o``;
- the indexer's objective: ``p[t, :] = stop_gradient(mean over the heads of
  their probabilities)``, ``L_I = mean_t KL(p[t, :] || softmax over S_t of
  I[t, :])``, summed over the layers; the loss is ``L_LM + L_I``;
- experts: router float32 over all ``num_experts_routed``, softmax, top
  ``num_experts_per_tok``, the chosen weights divided by their sum; the sum
  over the chosen experts that are HELD (``first_expert_held .. +
  num_experts``: the chip's share, as the configuration file states) of ``g *
  down(silu(gate(x)) * up(x))``, a dense loop over the held experts with
  masks; no shared expert;
- final ``rms``, untied head over the vocabulary slice; mean cross entropy.

Departures from a literal transcription, none of which changes a value: the
indexer's scores, the selection, attention and the objective run in blocks of
``ROWS`` query rows under a scan with a checkpoint each, the experts under a
scan with a checkpoint each, the head and the loss ``HEAD_ROWS`` positions at a
time, and each layer is recomputed in the backward. Every bfloat16 product of
the configuration goes through ``precision.matmul`` / ``precision.operand`` so
that the int8 control rounds both operands of all of them, the indexer's
included; the router's product stays float32 at full precision in every
precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .precision import HIGHEST, matmul, operand

ROWS = 128        # query rows scored, selected and attended at a time
HEAD_ROWS = 1024  # positions of the head and the loss


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=HIGHEST)


def _rms(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps))


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rotate(x, angle):
    """Half-rotation form; ``x``: ``[B, T, H, D]``, ``angle``: ``[B, T, D/2]``."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angle)[:, :, None, :], jnp.sin(angle)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _inv_freq(dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def mrope_angle(positions, cfg):
    """``[B, T, head_dim / 2]``: frequency ``i`` turns with the position id
    its section names (``mrope_section``: temporal, height, width)."""
    sections = cfg["rope_scaling"]["mrope_section"]
    inv = _inv_freq(cfg["head_dim"], float(cfg["rope_theta"]))
    if sum(sections) != inv.shape[0]:
        raise ValueError("mrope_section does not cover head_dim / 2")
    row = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                     total_repeat_length=inv.shape[0])
    pos = positions.astype(jnp.float32)[row]                 # [half, B, T]
    return jnp.moveaxis(pos, 0, -1) * inv


def index_scores(q_i, k_i, w, precision):
    """``I[b, t, s]`` for a block of query rows; ``q_i``: ``[B, R, J, D]``,
    ``k_i``: ``[B, T, D]``, ``w``: ``[B, R, J]``."""
    x = _einsum("brjd,bsd->brjs", q_i, k_i, precision)
    return jnp.sum(w[..., None] * jax.nn.relu(x), axis=2)


def select(scores, causal, top_k):
    """The selection of each row of ``scores`` (``[..., R, T]``) as a mask:
    the ``top_k`` largest of the causal positions, ties toward the lower
    position, by ``top_k``'s k-th value. Scores compare as floats do: zeros
    of both signs are one value (a rectified score is either, by its weights'
    signs) and tie."""
    k = min(top_k, scores.shape[-1])
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, k)[0][..., -1:]
    above = masked > kth
    equal = masked == kth
    missing = k - jnp.sum(above, axis=-1, keepdims=True)
    lowest = jnp.cumsum(equal, axis=-1) <= missing
    return (above | (equal & lowest)) & causal


def _indexer(g, p, positions, cfg, precision):
    sa = cfg["sa_config"]
    B, T, _ = g.shape
    J, D = sa["indexer_num_heads"], sa["indexer_head_dim"]
    q_i = matmul(g, p["wq"]["kernel"], precision).reshape(B, T, J, D)
    k_i = _layer_norm(matmul(g, p["wk"]["kernel"], precision), p["k_norm"],
                      cfg["rms_norm_eps"])
    angle = positions[0].astype(jnp.float32)[..., None] * _inv_freq(
        D, float(cfg["rope_theta"]))
    q_i = _rotate(q_i, angle)
    k_i = _rotate(k_i[:, :, None, :], angle)[:, :, 0]
    w = matmul(g, p["weights_proj"]["kernel"], precision) * (
        J ** -0.5 * D ** -0.5)
    return q_i, k_i, w


def _attention(h, p, positions, cfg, precision, selected=True):
    """``(attn(h) [B, T, d], sum over the rows of their KL term)``."""
    B, T, d = h.shape
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = matmul(h, p["q_proj"]["kernel"], precision).reshape(B, T, H, D)
    k = matmul(h, p["k_proj"]["kernel"], precision).reshape(B, T, KV, D)
    v = matmul(h, p["v_proj"]["kernel"], precision).reshape(B, T, KV, D)
    angle = mrope_angle(positions, cfg)
    q = _rotate(_rms(q, p["q_norm"]["scale"], eps), angle)
    k = _rotate(_rms(k, p["k_norm"]["scale"], eps), angle)
    q_i, k_i, w = _indexer(jax.lax.stop_gradient(h), p["indexer"], positions,
                           cfg, precision)
    group = H // KV
    q = q.reshape(B, T, KV, group, D)
    rows = min(ROWS, T)
    top_k = cfg["sa_config"]["topk"]

    @jax.checkpoint
    def block(start, xs):
        q_rows, qi_rows, w_rows = xs
        causal = (start + jnp.arange(rows))[:, None] >= jnp.arange(T)[None, :]
        scores = index_scores(qi_rows, k_i, w_rows, precision)   # [B, R, T]
        chosen = select(jax.lax.stop_gradient(scores), causal, top_k) \
            if selected else jnp.broadcast_to(causal, scores.shape)
        s = _einsum("bqjgd,bkjd->bjgqk", q_rows, k, precision) * D ** -0.5
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None, None], s, -jnp.inf), axis=-1)
        out = _einsum("bjgqk,bkjd->bqjgd", probs, v, precision)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))
        logq = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
        kl = jnp.where(chosen & (target > 0),
                       target * (jnp.log(jnp.where(target > 0, target, 1.0))
                                 - jnp.where(chosen, logq, 0.0)), 0.0)
        return start + rows, (out, jnp.sum(kl))

    n = T // rows
    split = lambda a: jnp.moveaxis(a.reshape(B, n, rows, *a.shape[2:]), 1, 0)
    _, (out, kl) = jax.lax.scan(block, 0, (split(q), split(q_i), split(w)))
    a = jnp.moveaxis(out, 0, 1).reshape(B, T, H * D)
    return matmul(a, p["o_proj"]["kernel"], precision), jnp.sum(kl)


def route(x, p, cfg):
    """``(weights [S, k], ids [S, k])`` of the tokens ``x`` (``[S, d]``).
    The router is float32 at full precision in every precision: which
    experts a token goes to is not a product to be rounded."""
    logits = jnp.matmul(x, p["router"]["kernel"], precision=HIGHEST)
    weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids


def routed(h, p, cfg, precision):
    """The held experts' part of the layer's result."""
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


def _layer(x, p, positions, cfg, precision, selected):
    eps = cfg["rms_norm_eps"]
    mixed, kl = _attention(_rms(x, p["input_layernorm"]["scale"], eps),
                           p["self_attn"], positions, cfg, precision, selected)
    x = x + mixed
    h = _rms(x, p["post_attention_layernorm"]["scale"], eps)
    return x + routed(h, p["mlp"], cfg, precision), kl


def text_positions(tokens):
    """The three position rows of text: ``arange(T)`` in each."""
    B, T = tokens.shape
    return jnp.broadcast_to(jnp.arange(T), (3, B, T))


def hidden(params, tokens, cfg, precision="highest", remat=False,
           positions=None, selected=True):
    """``(x [B, T, hidden] after the final norm, L_I)``: the indexer's
    objective is the mean over the rows of their KL term, summed over the
    layers. ``selected=False`` lets every causal key through (the dense model
    the selection is told from in the tests)."""
    if positions is None:
        positions = text_positions(tokens)
    x = params["embed_tokens"]["embedding"][tokens]
    layer = _layer
    if remat:
        layer = jax.checkpoint(_layer, static_argnums=(3, 4, 5))
    index_loss = 0.0
    for i in range(cfg["num_hidden_layers"]):
        x, kl = layer(x, params[f"layer_{i}"], positions, cfg, precision,
                      selected)
        index_loss = index_loss + kl / (tokens.shape[0] * tokens.shape[1])
    return _rms(x, params["norm"]["scale"], cfg["rms_norm_eps"]), index_loss


def logits(params, tokens, cfg, precision="highest", remat=False,
           positions=None, selected=True):
    """``(logits [B, T, vocab] float32, L_I)``."""
    x, index_loss = hidden(params, tokens, cfg, precision, remat, positions,
                           selected)
    return matmul(x, params["lm_head"]["kernel"], precision), index_loss


def loss_terms(params, batch, cfg, precision="highest", positions=None):
    """``(L_LM, L_I)`` of a block of rows. The head and the loss are computed
    ``HEAD_ROWS`` positions at a time, each recomputed in the backward."""
    tokens, labels = batch
    x, index_loss = hidden(params, tokens, cfg, precision, remat=True,
                           positions=positions)
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
    return -total / (B * T), index_loss


def loss(params, batch, cfg, precision="highest"):
    """``L_LM + L_I`` (rows are independent and every row has ``T`` queries,
    so the mean over blocks of rows is the batch's loss)."""
    lm, index = loss_terms(params, batch, cfg, precision)
    return lm + index


def block_rows(cfg, per_chip_batch):
    """Rows the loss may be computed on at a time: one 16384-token sequence's
    scratch (a block of ``ROWS`` query rows is ``heads x ROWS x T`` float32
    scores, 268 MB, a few times over) beside the harness's four float32
    trees."""
    return 1
