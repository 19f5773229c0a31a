"""Qwen3-Next-style hybrid decoder (``horovod_tpu/models/qwen3_next.py``): Gated
DeltaNet layers, a gated attention layer every ``full_attention_interval``-th,
and a dropless top-k expert layer of which this chip holds a share.

The benchmark makes the weights (``param_spec`` + ``weights.make_params``); the
program supplies the model and ``hvd.make_train_step``. The counts below are
the required operations and bytes of the configuration's mathematics at the
cell's shapes; nothing here reads the program.
"""

from __future__ import annotations

from ..weights import Leaf
from .gpt_dense import first_gradient, optimizer  # the same AdamW, the same state

REFERENCE = "qwen3_next"


def dims(cfg):
    m = dict(
        d=cfg["hidden_size"], L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
        every=cfg["full_attention_interval"],
        h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"],
        hk=cfg["linear_num_key_heads"], hv=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        taps=cfg["linear_conv_kernel_dim"],
        E=cfg["num_experts_routed"], held=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        fs=cfg["shared_expert_intermediate_size"],
    )
    m["attn_layers"] = sum(1 for i in range(m["L"]) if is_attention(cfg, i))
    m["gdn_layers"] = m["L"] - m["attn_layers"]
    return m


def is_attention(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def param_spec(cfg):
    """The Qwen3NextLM parameter tree, leaf for leaf."""
    m = dims(cfg)
    d, std = m["d"], cfg.get("initializer_range", 0.02)
    w = lambda *shape: Leaf(tuple(shape), "normal", std)
    zeros = lambda *shape: Leaf(tuple(shape), "zeros")
    ones = lambda *shape: Leaf(tuple(shape), "ones")
    kernel = lambda *shape: {"kernel": w(*shape)}
    norm = lambda n: {"scale": zeros(n)}     # zero-centred: weight 1 + scale
    spec = {
        "embed_tokens": {"embedding": w(m["V"], d)},
        "norm": norm(d),
        "lm_head": kernel(d, m["V"]),
    }
    qk, vz = m["hk"] * m["dk"], m["hv"] * m["dv"]
    for i in range(m["L"]):
        if is_attention(cfg, i):
            mixer = {"self_attn": {
                "q_proj": kernel(d, 2 * m["h"] * m["hd"]),   # query and gate
                "k_proj": kernel(d, m["kv"] * m["hd"]),
                "v_proj": kernel(d, m["kv"] * m["hd"]),
                "o_proj": kernel(m["h"] * m["hd"], d),
                "q_norm": norm(m["hd"]), "k_norm": norm(m["hd"]),
            }}
        else:
            mixer = {"linear_attn": {
                "in_proj_qkvz": kernel(d, 2 * qk + 2 * vz),
                "in_proj_ba": kernel(d, 2 * m["hv"]),
                "conv": {"kernel": w(m["taps"], 2 * qk + vz)},
                "A_log": zeros(m["hv"]), "dt_bias": zeros(m["hv"]),
                "norm": {"scale": ones(m["dv"])},
                "out_proj": kernel(vz, d),
            }}
        spec[f"layer_{i}"] = {
            "input_norm": norm(d), "post_norm": norm(d), **mixer,
            "mlp": {
                "router": kernel(d, m["E"]),
                "experts": {"gate": w(m["held"], d, m["f"]),
                            "up": w(m["held"], d, m["f"]),
                            "down": w(m["held"], m["f"], d)},
                "shared_gate_proj": kernel(d, m["fs"]),
                "shared_up_proj": kernel(d, m["fs"]),
                "shared_down_proj": kernel(m["fs"], d),
                "shared_gate": kernel(d, 1),
            },
        }
    return spec


def expected_held_per_token(cfg) -> float:
    """Of a token's ``k`` chosen experts, how many are held here when the
    choice is uniform over all of them (seeded weights route so)."""
    m = dims(cfg)
    return m["k"] * m["held"] / m["E"]


def matmul_params_per_token(cfg) -> float:
    """Weights a token is multiplied by: every layer's mixer, router and
    shared expert, the expected held experts it is routed to, and the head.
    The embedding is a gather; the convolution, the norms and the rule's own
    products are counted apart or not at all."""
    m = dims(cfg)
    d = m["d"]
    gdn = (d * (2 * m["hk"] * m["dk"] + 2 * m["hv"] * m["dv"])
           + d * 2 * m["hv"] + m["hv"] * m["dv"] * d)
    attn = (d * 2 * m["h"] * m["hd"] + 2 * d * m["kv"] * m["hd"]
            + m["h"] * m["hd"] * d)
    moe = (d * m["E"] + 3 * d * m["fs"] + d
           + expected_held_per_token(cfg) * 3 * d * m["f"])
    return (m["gdn_layers"] * gdn + m["attn_layers"] * attn + m["L"] * moe
            + d * m["V"])


def train_ops_per_step(cfg, traffic, batch_per_chip) -> float:
    """Required operations of one optimizer step on one chip: 6 per multiplied
    weight per token (the expected 0.625 held experts a token among them),
    causal attention at the attention layers (2 * 2 * T^2 / 2 * h * hd forward,
    times three with the backward) and the delta rule's recurrence at the
    others (``gdn_fwd_cost``'s operations, times three)."""
    m = dims(cfg)
    T = traffic["seq_len"]
    tokens = batch_per_chip * T
    attn = 6.0 * m["attn_layers"] * batch_per_chip * T * T * m["h"] * m["hd"]
    rule = 3.0 * gdn_fwd_cost(cfg, traffic, batch_per_chip)[0]
    return 6.0 * matmul_params_per_token(cfg) * tokens + attn + rule


def attn_fwd_calls(cfg) -> int:
    """The forward flash kernel's calls in ONE forward pass, one an attention
    layer: what ``attn_fwd_cost`` is the least cost of. With recomputation on a step
    runs the pass twice, and ``attn_fwd_roofline`` holds one pass's bound
    against one pass's share of the kernel's time."""
    return dims(cfg)["attn_layers"]


def attn_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward flash kernel calls of one
    step on one chip (the attention layers): causal QK^T and PV over the 16
    query heads; q, k, v (as the kernel is fed them: the 2 key/value heads
    repeated to 16) read and the output written once in bf16."""
    m = dims(cfg)
    T = traffic["seq_len"]
    width = m["h"] * m["hd"]
    ops = 2.0 * m["attn_layers"] * batch_per_chip * T * T * width
    bytes_ = 4.0 * m["attn_layers"] * batch_per_chip * T * width * 2
    return ops, bytes_


def gdn_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward gated delta rule of one step
    on one chip (all DeltaNet layers), by the per-token recurrence: per token
    and value head the decay of the state (d_k * d_v), S^T k, the rank-one
    update and S^T q (2 * d_k * d_v each): 7 * d_k * d_v. Bytes: q, k
    (repeated to the value heads), v read and o written once in bf16, g and
    beta read in f32; the state stays on the chip."""
    m = dims(cfg)
    tokens = batch_per_chip * traffic["seq_len"]
    per_head = 7.0 * m["dk"] * m["dv"]
    ops = m["gdn_layers"] * tokens * m["hv"] * per_head
    bytes_ = m["gdn_layers"] * tokens * m["hv"] * (
        (2 * m["dk"] + 2 * m["dv"]) * 2 + 2 * 4)
    return ops, bytes_


def moe_experts_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward grouped products of one step
    on one chip (all layers): the expected (token, expert) pairs held here
    times the three matrices of an expert (6 * d * f operations a pair);
    the held experts' weights read once in bf16, each pair's row read (d)
    and written (d) in bf16."""
    m = dims(cfg)
    tokens = batch_per_chip * traffic["seq_len"]
    pairs = tokens * expected_held_per_token(cfg)
    ops = m["L"] * pairs * 6.0 * m["d"] * m["f"]
    bytes_ = m["L"] * (m["held"] * 3 * m["d"] * m["f"] * 2
                       + pairs * 2 * m["d"] * 2)
    return ops, bytes_


def make_batches(cfg, traffic, global_batch, seed, n):
    """``n`` host batches of uniform random tokens and labels over the
    vocabulary slice, all rows different, from the seed."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 1])
    shape = (n, global_batch, traffic["seq_len"])
    tokens = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    labels = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    return [(tokens[i], labels[i]) for i in range(n)]


def model_config(cfg):
    """The configuration file's keys as ``Qwen3NextConfig``'s."""
    from horovod_tpu.models.qwen3_next import Qwen3NextConfig

    m = dims(cfg)
    return Qwen3NextConfig(
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"],
        full_attention_interval=m["every"], n_heads=m["h"],
        n_kv_heads=m["kv"], head_dim=m["hd"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        linear_k_heads=m["hk"], linear_v_heads=m["hv"],
        linear_k_dim=m["dk"], linear_v_dim=m["dv"], conv_kernel=m["taps"],
        n_experts=m["E"], experts_held=m["held"],
        first_expert=cfg.get("first_expert_held", 0), top_k=m["k"],
        expert_dim=m["f"], shared_dim=m["fs"],
        norm_topk=cfg["norm_topk_prob"], eps=cfg["rms_norm_eps"],
        init_std=cfg.get("initializer_range", 0.02),
        chunk=cfg["train"].get("gdn_chunk", 64),
        remat=cfg["train"].get("remat", True),
    )


def build_train(cfg, traffic, step_options, mesh):
    """``(step, tx)``: the user's call, ``hvd.make_train_step`` over the
    flax model with its defaults."""
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu.models.qwen3_next import Qwen3NextLM

    model = Qwen3NextLM(model_config(cfg))

    def loss_fn(p, batch):
        tokens, labels = batch
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    tx = hvd.DistributedOptimizer(optimizer(cfg))
    return hvd.make_train_step(loss_fn, tx, mesh, **step_options), tx
