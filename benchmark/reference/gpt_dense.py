"""Plain float32 reference of the GPT-2-style decoder: no kernel, no cache, no
batching tricks. It imports nothing of the program. Learned positions,
pre-LayerNorm blocks, full causal multi-head attention, tanh GELU, an untied
head without bias, attention projections without bias (the repo's departures
from GPT-2, listed in the configuration file).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .precision import HIGHEST, matmul, operand


def _layer_norm(x, p, eps):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _block(x, p, n_head, eps, precision):
    B, T, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, p["ln_1"], eps)
    att = p["attention"]
    split = lambda y: y.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)
    q = split(matmul(h, att["query"]["kernel"], precision))
    k = split(matmul(h, att["key"]["kernel"], precision))
    v = split(matmul(h, att["value"]["kernel"], precision))
    scores = jnp.einsum("bhqd,bhkd->bhqk", operand(q, precision),
                        operand(k, precision), precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bhqk,bhkd->bhqd", operand(probs, precision),
                   operand(v, precision), precision=HIGHEST)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, d)
    x = x + matmul(a, att["out"]["kernel"], precision)
    h = _layer_norm(x, p["ln_2"], eps)
    mlp = p["mlp"]
    u = matmul(h, mlp["up"]["kernel"], precision) + mlp["up"]["bias"]
    u = jax.nn.gelu(u, approximate=True)
    y = matmul(u, mlp["down"]["kernel"], precision) + mlp["down"]["bias"]
    return x + y


def logits(params, tokens, cfg, precision="highest", remat=False):
    """[B, T] tokens -> [B, T, vocab] float32 logits."""
    eps = cfg.get("layer_norm_epsilon_as_run", cfg["layer_norm_epsilon"])
    B, T = tokens.shape
    x = params["embeddings"]["embedding"][tokens]
    x = x + params["pos_embeddings"]["embedding"][:T][None]
    block = _block
    if remat:
        block = jax.checkpoint(_block, static_argnums=(2, 3, 4))
    for i in range(cfg["n_layer"]):
        x = block(x, params[f"block_{i}"], cfg["n_head"], eps, precision)
    x = _layer_norm(x, params["ln_f"], eps)
    return matmul(x, params["lm_head"]["kernel"], precision)


def loss(params, batch, cfg, precision="highest"):
    """Mean next-token cross entropy of a block of rows (rows are
    independent, so the mean over blocks is the batch's loss)."""
    tokens, labels = batch
    lg = logits(params, tokens, cfg, precision, remat=True)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


def block_rows(cfg, per_chip_batch):
    """Rows the loss may be computed on at a time."""
    return 1
