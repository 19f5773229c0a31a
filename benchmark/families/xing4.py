"""Xing4.0-style decoder (``horovod_tpu/models/xing4.py``): latent attention
with keys of 192 and values of 128, four residual streams mixed round every
sublayer by Sinkhorn-normalised matrices, a dense feed-forward in the first
``first_k_dense_replace`` layers and, in the others, a dropless top-k expert
layer routed by sigmoid scores with a selection bias, of which this chip
holds a share, beside a shared expert; untied head.

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

REFERENCE = "xing4"


def dims(cfg):
    n = cfg["hc_mult"]
    return dict(
        d=cfg["hidden_size"], V=cfg["vocab_size"],
        L=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        h=cfg["num_attention_heads"], ql=cfg["q_lora_rank"],
        kl=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        fd=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        E=cfg["n_routed_experts_routed"], held=cfg["n_routed_experts"],
        k=cfg["num_experts_per_tok"], n=n, maps=2 * n + n * n,
        sparse_layers=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
    )


def param_spec(cfg):
    """The Xing4LM parameter tree, leaf for leaf."""
    m = dims(cfg)
    d, std = m["d"], cfg.get("initializer_range", 0.02)
    w = lambda *shape: Leaf(tuple(shape), "normal", std)
    kernel = lambda *shape: {"kernel": w(*shape)}
    norm = lambda n: {"scale": Leaf((n,), "ones")}
    swiglu = lambda f: {"w1": kernel(d, f), "w3": kernel(d, f),
                        "w2": kernel(f, d)}
    mix = lambda: {
        "phi": Leaf((m["n"] * d, m["maps"]), "normal", cfg["hc_phi_std"]),
        "alpha": Leaf((3,), "ones"),
        "b": Leaf((m["maps"],), "normal", cfg["hc_b_std"]),
    }
    spec = {"embed_tokens": {"embedding": w(m["V"], d)}, "norm": norm(d),
            "lm_head": kernel(d, m["V"])}
    for i in range(m["L"]):
        layer = {
            "attn_hc": mix(), "ffn_hc": mix(),
            "input_layernorm": norm(d), "post_attention_layernorm": norm(d),
            "self_attn": {
                "q_a_proj": kernel(d, m["ql"]), "q_a_layernorm": norm(m["ql"]),
                "q_b_proj": kernel(m["ql"], m["h"] * (m["dn"] + m["dr"])),
                "kv_a_proj": kernel(d, m["kl"] + m["dr"]),
                "kv_a_layernorm": norm(m["kl"]),
                "kv_b_proj": kernel(m["kl"], m["h"] * (m["dn"] + m["dv"])),
                "o_proj": kernel(m["h"] * m["dv"], d),
            },
        }
        if i < m["dense"]:
            layer["mlp"] = swiglu(m["fd"])
        else:
            layer["mlp"] = {
                "router": kernel(d, m["E"]),
                "expert_bias": Leaf((m["E"],), "normal",
                                    cfg["expert_bias_std"]),
                "experts": {"gate": w(m["held"], d, m["f"]),
                            "up": w(m["held"], d, m["f"]),
                            "down": w(m["held"], m["f"], d)},
            }
            layer["shared_expert"] = swiglu(m["fs"])
        spec[f"layer_{i}"] = layer
    return spec


def expected_held_per_token(cfg) -> float:
    """Of a token's ``k`` chosen experts, how many are held here when the
    choice is uniform over all of them (seeded weights route so)."""
    m = dims(cfg)
    return m["k"] * m["held"] / m["E"]


def matmul_params_per_token(cfg) -> float:
    """Weights a token is multiplied by: every layer's five latent-attention
    matrices and its two ``phi``, the dense feed-forward or the router, the
    shared expert and the expected held experts it is routed to, and the
    head. The lookup is a gather; the streams' weighted sums, the Sinkhorn
    rounds and the norms are counted apart or not at all."""
    m = dims(cfg)
    d = m["d"]
    attn = (d * m["ql"] + m["ql"] * m["h"] * (m["dn"] + m["dr"])
            + d * (m["kl"] + m["dr"])
            + m["kl"] * m["h"] * (m["dn"] + m["dv"]) + m["h"] * m["dv"] * d)
    mixes = 2 * m["n"] * d * m["maps"]
    sparse = (d * m["E"] + 3 * d * m["fs"]
              + expected_held_per_token(cfg) * 3 * d * m["f"])
    return (m["L"] * (attn + mixes) + m["dense"] * 3 * d * m["fd"]
            + m["sparse_layers"] * sparse + d * m["V"])


def attn_fwd_calls(cfg) -> int:
    """The forward flash kernel's calls in ONE forward pass, one a layer:
    what ``attn_fwd_cost`` is the least cost of. With recomputation on a step
    runs the pass twice, and ``attn_fwd_roofline`` holds one pass's bound
    against one pass's share of the kernel's time."""
    return dims(cfg)["L"]


def attn_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward flash kernel calls of one
    step on one chip (all layers): over the causal half of the pairs, QK^T
    at the keys' width (nope + rope, 192) and PV at the values' (128), so
    ``192 + 128`` multiply-adds a pair and head; q and k (192) and v (128)
    read as the kernel is fed them (the one rotary key head broadcast to the
    query heads) and the output (128) written once in bf16."""
    m = dims(cfg)
    T = traffic["seq_len"]
    qk, v = m["dn"] + m["dr"], m["dv"]
    ops = 1.0 * m["L"] * batch_per_chip * T * T * m["h"] * (qk + v)
    bytes_ = 2.0 * m["L"] * batch_per_chip * T * m["h"] * (2 * qk + 2 * v)
    return ops, bytes_


def train_ops_per_step(cfg, traffic, batch_per_chip) -> float:
    """Required operations of one optimizer step on one chip: 6 per multiplied
    weight per token (the expected held experts a token among them) and
    causal attention forward times three with the backward. Recomputed work
    is not in it."""
    T = traffic["seq_len"]
    attn, _ = attn_fwd_cost(cfg, traffic, batch_per_chip)
    return 6.0 * matmul_params_per_token(cfg) * batch_per_chip * T + 3 * attn


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


def hc_mix_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of one forward of the stream mixes on one
    chip (two a layer): a sublayer reads the n streams once and writes them
    once in bf16 (``2 * n * C * 2`` bytes a token), and the ``[n C, 2 n + n
    n]`` product that makes the maps (the weighted sums and the Sinkhorn
    rounds are some 30 operations an element of the streams and under it by
    far). Memory-bound by two orders."""
    m = dims(cfg)
    tokens = batch_per_chip * traffic["seq_len"]
    mixes = 2 * m["L"]
    ops = mixes * tokens * 2.0 * m["n"] * m["d"] * m["maps"]
    bytes_ = mixes * tokens * 2.0 * m["n"] * m["d"] * 2
    return ops, bytes_


def model_config(cfg):
    """The configuration file's keys as ``Xing4Config``'s."""
    from horovod_tpu.models.xing4 import Xing4Config

    m, r = dims(cfg), cfg["rope_scaling"]
    if r["type"] != "yarn" or cfg["scoring_func"] != "sigmoid" or (
            cfg["n_group"], cfg["topk_group"]) != (1, 1):
        raise ValueError("xing4 runs YaRN frequencies and ungrouped sigmoid "
                         "routing; the configuration asks for another")
    return Xing4Config(
        vocab_size=m["V"], n_layers=m["L"], n_dense_layers=m["dense"],
        d_model=m["d"], n_heads=m["h"], q_lora_rank=m["ql"],
        kv_lora_rank=m["kl"], qk_nope_dim=m["dn"], qk_rope_dim=m["dr"],
        v_head_dim=m["dv"], rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(r["factor"]),
        rope_original_max=r["original_max_position_embeddings"],
        rope_beta_fast=float(r["beta_fast"]),
        rope_beta_slow=float(r["beta_slow"]),
        rope_mscale_value=float(r["mscale"]),
        rope_mscale_all_dim=float(r["mscale_all_dim"]),
        dense_dim=m["fd"], n_experts=m["E"], experts_held=m["held"],
        first_expert=cfg.get("first_expert_held", 0), top_k=m["k"],
        expert_dim=m["f"], shared_dim=m["fs"],
        norm_topk=cfg["norm_topk_prob"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        route_norm_eps=cfg["route_norm_eps"], hc_mult=m["n"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                  float(cfg["mhc_h_res_clamp_max"])),
        eps=cfg["rms_norm_eps"],
        init_std=cfg.get("initializer_range", 0.02),
        remat=cfg["train"].get("remat", True),
    )


def build_train(cfg, traffic, step_options, mesh):
    """``(step, tx)``: the user's call, ``hvd.make_train_step`` over the
    flax model with its defaults."""
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu.models.xing4 import Xing4LM

    model = Xing4LM(model_config(cfg))

    def loss_fn(p, batch):
        tokens, labels = batch
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    tx = hvd.DistributedOptimizer(optimizer(cfg))
    return hvd.make_train_step(loss_fn, tx, mesh, **step_options), tx
