"""LFM2-MoE-style hybrid decoder (``horovod_tpu/models/lfm2_moe.py``): gated
short-convolution layers and grouped-query attention layers in the order
``layer_types`` gives, a dense feed-forward in the first ``num_dense_layers``
layers and a dropless top-k expert layer, routed by sigmoid scores with a
selection bias, of which this chip holds a share, in the others; the head is
the embedding's transpose.

The benchmark makes the weights (``param_spec`` + ``weights.make_params``); the
program supplies the model and ``hvd.make_train_step``. The counts below are
the required operations and bytes of the configuration's mathematics at the
cell's shapes; nothing here reads the program.
"""

from __future__ import annotations

from ..weights import Leaf
# the same AdamW and state as the other families; uniform tokens over the slice
from .gpt_dense import first_gradient, optimizer
from .qwen3_next import make_batches

REFERENCE = "lfm2_moe"
CONV, ATTENTION = "conv", "full_attention"


def dims(cfg):
    m = dict(
        d=cfg["hidden_size"], V=cfg["vocab_size"],
        kinds=tuple(cfg["layer_types"]), dense=cfg["num_dense_layers"],
        h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["hidden_size"] // cfg["num_attention_heads"],
        taps=cfg["conv_L_cache"], fd=cfg["intermediate_size"],
        E=cfg["num_experts_routed"], held=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
    )
    if len(m["kinds"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    m["L"] = len(m["kinds"])
    m["attn_layers"] = m["kinds"].count(ATTENTION)
    m["conv_layers"] = m["kinds"].count(CONV)
    m["sparse_layers"] = m["L"] - m["dense"]
    return m


def param_spec(cfg):
    """The Lfm2MoeLM parameter tree, leaf for leaf."""
    m = dims(cfg)
    d, std = m["d"], cfg.get("initializer_range", 0.02)
    w = lambda *shape: Leaf(tuple(shape), "normal", std)
    kernel = lambda *shape: {"kernel": w(*shape)}
    norm = lambda n: {"scale": Leaf((n,), "ones")}
    spec = {"embed_tokens": {"embedding": w(m["V"], d)}, "norm": norm(d)}
    for i, kind in enumerate(m["kinds"]):
        if kind == ATTENTION:
            mixer = {"self_attn": {
                "q_proj": kernel(d, m["h"] * m["hd"]),
                "k_proj": kernel(d, m["kv"] * m["hd"]),
                "v_proj": kernel(d, m["kv"] * m["hd"]),
                "out_proj": kernel(m["h"] * m["hd"], d),
                "q_layernorm": norm(m["hd"]), "k_layernorm": norm(m["hd"]),
            }}
        else:
            mixer = {"conv": {
                "in_proj": kernel(d, 3 * d),
                "conv": kernel(m["taps"], d),
                "out_proj": kernel(d, d),
            }}
        if i < m["dense"]:
            ffn = {"w1": kernel(d, m["fd"]), "w3": kernel(d, m["fd"]),
                   "w2": kernel(m["fd"], d)}
        else:
            ffn = {
                "router": kernel(d, m["E"]),
                "expert_bias": Leaf((m["E"],), "normal",
                                    cfg["expert_bias_std"]),
                "experts": {"gate": w(m["held"], d, m["f"]),
                            "up": w(m["held"], d, m["f"]),
                            "down": w(m["held"], m["f"], d)},
            }
        spec[f"layer_{i}"] = {"operator_norm": norm(d), "ffn_norm": norm(d),
                              **mixer, "feed_forward": ffn}
    return spec


def expected_held_per_token(cfg) -> float:
    """Of a token's ``k`` chosen experts, how many are held here when the
    choice is uniform over all of them (seeded weights route so)."""
    m = dims(cfg)
    return m["k"] * m["held"] / m["E"]


def matmul_params_per_token(cfg) -> float:
    """Weights a token is multiplied by: every layer's mixer, the dense
    feed-forward or the router and the expected held experts it is routed
    to, and the head (the embedding once more, as a product). The lookup is
    a gather; the gates, the taps and the norms are counted apart or not at
    all."""
    m = dims(cfg)
    d = m["d"]
    conv = d * 3 * d + d * d
    attn = 2 * d * m["h"] * m["hd"] + 2 * d * m["kv"] * m["hd"]
    sparse = d * m["E"] + expected_held_per_token(cfg) * 3 * d * m["f"]
    return (m["conv_layers"] * conv + m["attn_layers"] * attn
            + m["dense"] * 3 * d * m["fd"] + m["sparse_layers"] * sparse
            + d * m["V"])


def train_ops_per_step(cfg, traffic, batch_per_chip) -> float:
    """Required operations of one optimizer step on one chip: 6 per multiplied
    weight per token (the expected held experts a token among them) and
    causal attention at the attention layers (2 * 2 * T^2 / 2 * h * hd
    forward, times three with the backward)."""
    m = dims(cfg)
    T = traffic["seq_len"]
    attn = 6.0 * m["attn_layers"] * batch_per_chip * T * T * m["h"] * m["hd"]
    return 6.0 * matmul_params_per_token(cfg) * batch_per_chip * T + attn


def attn_fwd_calls(cfg) -> int:
    """The forward flash kernel's calls in ONE forward pass, one an attention
    layer: what ``attn_fwd_cost`` is the least cost of. With recomputation on a step
    runs the pass twice, and ``attn_fwd_roofline`` holds one pass's bound
    against one pass's share of the kernel's time."""
    return dims(cfg)["attn_layers"]


def attn_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward flash kernel calls of one
    step on one chip (the attention layers): causal QK^T and PV over the
    query heads; q, k, v (as the kernel is fed them: the key/value heads
    repeated to the query heads) read and the output written once in bf16."""
    m = dims(cfg)
    T = traffic["seq_len"]
    width = m["h"] * m["hd"]
    ops = 2.0 * m["attn_layers"] * batch_per_chip * T * T * width
    bytes_ = 4.0 * m["attn_layers"] * batch_per_chip * T * width * 2
    return ops, bytes_


def short_conv_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of one forward of the convolution mixers'
    own mechanism on one chip (all conv layers; the projections are not in
    it): per token and channel the two gates and the taps (``2 * taps + 1``
    operations); B, C and u read and the gated result written once in bf16,
    ``8 d`` bytes a token a layer. Memory-bound by three orders."""
    m = dims(cfg)
    tokens = batch_per_chip * traffic["seq_len"]
    ops = m["conv_layers"] * tokens * m["d"] * (2.0 * m["taps"] + 1)
    bytes_ = m["conv_layers"] * tokens * m["d"] * 4 * 2.0
    return ops, bytes_


def moe_experts_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward grouped products of one step
    on one chip (the sparse layers): the expected (token, expert) pairs held
    here times the three matrices of an expert (6 * d * f operations a
    pair); the held experts' weights read once in bf16, each pair's row read
    (d) and written (d) in bf16."""
    m = dims(cfg)
    tokens = batch_per_chip * traffic["seq_len"]
    pairs = tokens * expected_held_per_token(cfg)
    ops = m["sparse_layers"] * pairs * 6.0 * m["d"] * m["f"]
    bytes_ = m["sparse_layers"] * (m["held"] * 3 * m["d"] * m["f"] * 2
                                   + pairs * 2 * m["d"] * 2)
    return ops, bytes_


def model_config(cfg):
    """The configuration file's keys as ``Lfm2MoeConfig``'s."""
    from horovod_tpu.models.lfm2_moe import Lfm2MoeConfig

    m = dims(cfg)
    return Lfm2MoeConfig(
        vocab_size=m["V"], layer_types=m["kinds"], n_dense_layers=m["dense"],
        d_model=m["d"], n_heads=m["h"], n_kv_heads=m["kv"], head_dim=m["hd"],
        rope_theta=float(cfg["rope_theta"]), conv_kernel=m["taps"],
        dense_dim=m["fd"], n_experts=m["E"], experts_held=m["held"],
        first_expert=cfg.get("first_expert_held", 0), top_k=m["k"],
        expert_dim=m["f"], norm_topk=cfg["norm_topk_prob"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        use_expert_bias=cfg["use_expert_bias"], eps=cfg["norm_eps"],
        init_std=cfg.get("initializer_range", 0.02),
        remat=cfg["train"].get("remat", True),
    )


def build_train(cfg, traffic, step_options, mesh):
    """``(step, tx)``: the user's call, ``hvd.make_train_step`` over the
    flax model with its defaults."""
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu.models.lfm2_moe import Lfm2MoeLM

    model = Lfm2MoeLM(model_config(cfg))

    def loss_fn(p, batch):
        tokens, labels = batch
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    tx = hvd.DistributedOptimizer(optimizer(cfg))
    return hvd.make_train_step(loss_fn, tx, mesh, **step_options), tx
