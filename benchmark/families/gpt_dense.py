"""GPT-2-style dense decoder (``horovod_tpu/models/transformer.py``).

The benchmark makes the weights (``param_spec`` + ``weights.make_params``); the
program supplies the model and ``hvd.make_train_step``.
"""

from __future__ import annotations

from ..weights import Leaf

REFERENCE = "gpt_dense"


def dims(cfg):
    return dict(d=cfg["n_embd"], h=cfg["n_head"], L=cfg["n_layer"],
                T=cfg["n_positions"], V=cfg["vocab_size"])


def param_spec(cfg):
    """The TransformerLM parameter tree, leaf for leaf."""
    m = dims(cfg)
    d, std = m["d"], cfg.get("initializer_range", 0.02)
    w = lambda *shape: Leaf(tuple(shape), "normal", std)
    zeros = lambda *shape: Leaf(tuple(shape), "zeros")
    ln = lambda: {"scale": Leaf((d,), "ones"), "bias": zeros(d)}
    spec = {
        "embeddings": {"embedding": w(m["V"], d)},
        "pos_embeddings": {"embedding": w(m["T"], d)},
        "ln_f": ln(),
        "lm_head": {"kernel": w(d, m["V"])},
    }
    for i in range(m["L"]):
        spec[f"block_{i}"] = {
            "ln_1": ln(),
            "attention": {k: {"kernel": w(d, d)}
                          for k in ("query", "key", "value", "out")},
            "ln_2": ln(),
            "mlp": {"up": {"kernel": w(d, 4 * d), "bias": zeros(4 * d)},
                    "down": {"kernel": w(4 * d, d), "bias": zeros(d)}},
        }
    return spec


def matmul_params(cfg) -> int:
    """Weights that are multiplied: the blocks' matrices and the head. The
    token and position tables are gathers and count nothing."""
    m = dims(cfg)
    return m["L"] * 12 * m["d"] ** 2 + m["d"] * m["V"]


def train_ops_per_step(cfg, traffic, batch_per_chip) -> float:
    """Required operations of one optimizer step on one chip: 6 per multiplied
    weight per token, plus causal attention (the lower half of QK^T and PV:
    2 * 2 * T^2/2 * d per layer forward, times three for the backward)."""
    m = dims(cfg)
    T = traffic["seq_len"]
    tokens = batch_per_chip * T
    return (6.0 * matmul_params(cfg) * tokens
            + 6.0 * m["L"] * batch_per_chip * T * T * m["d"])


def attn_fwd_calls(cfg) -> int:
    """The forward flash kernel's calls in ONE forward pass, one a block:
    what ``attn_fwd_cost`` is the least cost of. With recomputation on a step
    runs the pass twice, and ``attn_fwd_roofline`` holds one pass's bound
    against one pass's share of the kernel's time."""
    return dims(cfg)["L"]


def attn_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward flash kernel calls of one
    step on one chip (all layers): causal QK^T and PV, q/k/v read and the
    output written once in bf16."""
    m = dims(cfg)
    T = traffic["seq_len"]
    ops = 2.0 * m["L"] * batch_per_chip * T * T * m["d"]
    bytes_ = 4.0 * m["L"] * batch_per_chip * T * m["d"] * 2
    return ops, bytes_


def make_batches(cfg, traffic, global_batch, seed, n):
    """``n`` host batches of uniform random tokens and labels, all rows
    different, from the seed."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 1])
    shape = (n, global_batch, traffic["seq_len"])
    tokens = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    labels = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    return [(tokens[i], labels[i]) for i in range(n)]


def optimizer(cfg):
    import optax

    o = cfg["train"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"gpt_dense trains with adamw, not {o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


def build_train(cfg, traffic, step_options, mesh):
    """``(step, tx)``: the user's call, ``hvd.make_train_step`` over the
    flax model with its defaults."""
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu.models.transformer import TransformerLM

    m = dims(cfg)
    model = TransformerLM(vocab_size=m["V"], d_model=m["d"], n_heads=m["h"],
                          n_layers=m["L"], max_len=m["T"])

    def loss_fn(p, batch):
        tokens, labels = batch
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    tx = hvd.DistributedOptimizer(optimizer(cfg))
    return hvd.make_train_step(loss_fn, tx, mesh, **step_options), tx


def first_gradient(cfg, opt_state):
    """The gradient the optimizer was given at step one, from Adam's first
    moment after that step: mu = (1 - b1) * g."""
    import jax

    mu = _find(opt_state, "mu")
    scale = 1.0 / (1.0 - cfg["train"]["optimizer"]["b1"])
    return jax.tree.map(lambda x: x * scale, mu)


def _find(state, attr):
    if hasattr(state, attr):
        return getattr(state, attr)
    if isinstance(state, (tuple, list)):
        for s in state:
            found = _find(s, attr)
            if found is not None:
                return found
    return None
