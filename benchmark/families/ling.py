"""Ling-3.0-style hybrid decoder (``horovod_tpu/models/ling.py``): Kimi-delta-
attention layers (a delta rule whose state decays per key channel under a
gate bounded below) with a latent-attention layer (keys 192, values 128, no
query bottleneck, one output gate a head) where ``layer_mixers`` says so, a
dense feed-forward in the first ``first_k_dense_replace`` layers and, in the
others, a dropless top-k expert layer routed by sigmoid scores with a
selection bias inside the best groups of experts, of which this chip holds a
share, beside a shared expert; untied head.

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

REFERENCE = "ling"
KDA, MLA = "kda", "mla"


def dims(cfg):
    m = dict(
        d=cfg["hidden_size"], V=cfg["vocab_size"],
        L=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        kinds=tuple(cfg["layer_mixers"]),
        h=cfg["num_attention_heads"], hd=cfg["head_dim"],
        taps=cfg["short_conv_kernel_size"],
        kl=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        fd=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        fs=cfg["num_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"],
        E=cfg["num_experts_routed"], held=cfg["num_experts"],
        k=cfg["num_experts_per_tok"],
    )
    if len(m["kinds"]) != m["L"] or set(m["kinds"]) - {KDA, MLA}:
        raise ValueError("layer_mixers does not list num_hidden_layers "
                         f"mixers, each {KDA!r} or {MLA!r}")
    # the list is the published rule from the first kept layer on
    first, every = cfg["first_layer_published"], cfg["layer_group_size"]
    if m["kinds"] != tuple(MLA if (first + i + 1) % every == 0 else KDA
                           for i in range(m["L"])):
        raise ValueError("layer_mixers is not layer_group_size's rule from "
                         "first_layer_published on")
    m["kda_layers"] = m["kinds"].count(KDA)
    m["mla_layers"] = m["kinds"].count(MLA)
    m["sparse_layers"] = m["L"] - m["dense"]
    m["inner"] = m["h"] * m["hd"]
    return m


def param_spec(cfg):
    """The LingLM parameter tree, leaf for leaf."""
    m = dims(cfg)
    d, std, inner = m["d"], cfg.get("initializer_range", 0.02), m["inner"]
    w = lambda *shape: Leaf(tuple(shape), "normal", std)
    kernel = lambda *shape: {"kernel": w(*shape)}
    norm = lambda n: {"scale": Leaf((n,), "ones")}
    swiglu = lambda f: {"w1": kernel(d, f), "w3": kernel(d, f),
                        "w2": kernel(f, d)}
    # the table alone is drawn wider (``seeded_embedding_std``), and the
    # gate's two leaves so that the channels' decays spread over the whole
    # of the gate's range (``seeded_dt_bias_std``): the configuration's file
    # says why
    table = Leaf((m["V"], d), "normal", cfg.get("seeded_embedding_std", std))
    spec = {"embed_tokens": {"embedding": table}, "norm": norm(d),
            "lm_head": kernel(d, m["V"])}
    for i, kind in enumerate(m["kinds"]):
        layer = {"input_layernorm": norm(d),
                 "post_attention_layernorm": norm(d)}
        if kind == KDA:
            layer["linear_attn"] = {
                **{f"{n}_proj": kernel(d, inner) for n in "qkvfg"},
                **{f"{n}_conv": kernel(m["taps"], inner) for n in "qkv"},
                "b_proj": kernel(d, m["h"]),
                "A_log": Leaf((m["h"],), "ones"),
                "dt_bias": Leaf((inner,), "normal",
                                cfg["seeded_dt_bias_std"]),
                "o_norm": norm(m["hd"]),
                "o_proj": kernel(inner, d),
            }
        else:
            layer["self_attn"] = {
                "q_proj": kernel(d, m["h"] * (m["dn"] + m["dr"])),
                "kv_a_proj": kernel(d, m["kl"] + m["dr"]),
                "kv_a_layernorm": norm(m["kl"]),
                "kv_b_proj": kernel(m["kl"], m["h"] * (m["dn"] + m["dv"])),
                "g_proj": kernel(d, m["h"]),
                "o_proj": kernel(m["h"] * m["dv"], d),
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
    choice is uniform over all of them (seeded weights route so; the group
    limit keeps it uniform, every group being as likely as another)."""
    m = dims(cfg)
    return m["k"] * m["held"] / m["E"]


def matmul_params_per_token(cfg) -> float:
    """Weights a token is multiplied by: a delta-attention layer's six
    ``d x H d_k`` matrices and ``b_proj``, the latent-attention layer's five
    (its gate among them), the dense feed-forward or the router, the shared
    expert and the expected held experts it is routed to, and the head. The
    lookup is a gather; the taps, the rule and the norms are counted apart
    or not at all."""
    m = dims(cfg)
    d = m["d"]
    kda = 6 * d * m["inner"] + d * m["h"]
    mla = (d * m["h"] * (m["dn"] + m["dr"]) + d * (m["kl"] + m["dr"])
           + m["kl"] * m["h"] * (m["dn"] + m["dv"]) + d * m["h"]
           + m["h"] * m["dv"] * d)
    sparse = (d * m["E"] + 3 * d * m["fs"]
              + expected_held_per_token(cfg) * 3 * d * m["f"])
    return (m["kda_layers"] * kda + m["mla_layers"] * mla
            + m["dense"] * 3 * d * m["fd"] + m["sparse_layers"] * sparse
            + d * m["V"])


def kda_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of one forward pass of the per-channel
    delta rule on one chip (all delta-attention layers), by the per-token
    recurrence, whatever implements it: per token and head the decay of the
    state (``d_k d_v``), ``S^T k``, the rank-one update and ``S^T q`` (``2
    d_k d_v`` each), ``7 d_k d_v``, and ``beta (v - .)`` (``2 d_v``). Bytes:
    q, k and v read and o written once in bf16, the gate (``d_k`` a head)
    and beta read in f32; the state stays on the chip."""
    m = dims(cfg)
    tokens = batch_per_chip * traffic["seq_len"]
    dk = dv = m["hd"]
    ops = m["kda_layers"] * tokens * m["h"] * (7.0 * dk * dv + 2 * dv)
    bytes_ = m["kda_layers"] * tokens * m["h"] * (
        (2 * dk + 2 * dv) * 2 + dk * 4 + 4)
    return ops, bytes_


def attn_fwd_calls(cfg) -> int:
    """The forward flash kernel's calls in ONE forward pass, one a latent-
    attention layer: what ``attn_fwd_cost`` is the least cost of. The layer's
    recomputation keeps the kernel's result, so a step makes no more."""
    return dims(cfg)["mla_layers"]


def attn_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward flash kernel calls of one
    step on one chip (the latent-attention layers): over the causal half of
    the pairs, QK^T at the keys' width (nope + rope, 192) and PV at the
    values' (128), so ``192 + 128`` multiply-adds a pair and head; q and k
    (192) and v (128) read as the kernel is fed them (the one rotary key head
    broadcast to the query heads) and the output (128) written once in
    bf16."""
    m = dims(cfg)
    T = traffic["seq_len"]
    qk, v = m["dn"] + m["dr"], m["dv"]
    ops = 1.0 * m["mla_layers"] * batch_per_chip * T * T * m["h"] * (qk + v)
    bytes_ = 2.0 * m["mla_layers"] * batch_per_chip * T * m["h"] * (
        2 * qk + 2 * v)
    return ops, bytes_


def train_ops_per_step(cfg, traffic, batch_per_chip) -> float:
    """Required operations of one optimizer step on one chip: 6 per multiplied
    weight per token (the expected held experts a token among them), causal
    attention forward times three with the backward, and the delta rule by
    its per-token recurrence (``kda_fwd_cost``'s operations, times three).
    Recomputed work is not in it."""
    T = traffic["seq_len"]
    attn, _ = attn_fwd_cost(cfg, traffic, batch_per_chip)
    rule, _ = kda_fwd_cost(cfg, traffic, batch_per_chip)
    return (6.0 * matmul_params_per_token(cfg) * batch_per_chip * T
            + 3 * attn + 3 * rule)


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
    """The configuration file's keys as ``LingConfig``'s."""
    from horovod_tpu.models.ling import LingConfig

    m = dims(cfg)
    if (cfg["score_function"], cfg["topk_method"]) != ("sigmoid", "noaux_tc") \
            or cfg["q_lora_rank"] is not None or cfg["rope_scaling"] \
            or not (cfg["kda_safe_gate"] and cfg["no_kda_lora"]
                    and cfg["rope_interleave"]) or cfg["use_mla_nope"] \
            or cfg["gated_attention_proj_granularity_type"] != "head_wise" \
            or any(cfg["expert_swiglu_limit_list"]
                   + cfg["share_expert_swiglu_limit_list"]):
        raise ValueError(
            "ling runs group-limited sigmoid routing, latent attention "
            "without a query bottleneck at a plain theta with rotary pairs "
            "of neighbours and a head-wise gate, a bounded full-rank KDA "
            "gate and no swiglu clamp; the configuration asks for another")
    return LingConfig(
        vocab_size=m["V"], n_layers=m["L"], n_dense_layers=m["dense"],
        layer_group_size=cfg["layer_group_size"], layer_kinds=m["kinds"],
        d_model=m["d"], n_heads=m["h"], head_dim=m["hd"],
        conv_kernel=m["taps"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        kv_lora_rank=m["kl"], qk_nope_dim=m["dn"], qk_rope_dim=m["dr"],
        v_head_dim=m["dv"], rope_theta=float(cfg["rope_theta"]),
        dense_dim=m["fd"], n_experts=m["E"], experts_held=m["held"],
        first_expert=cfg.get("first_expert_held", 0), top_k=m["k"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        expert_dim=m["f"], shared_dim=m["fs"],
        norm_topk=cfg["norm_topk_prob"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        eps=cfg["rms_norm_eps"],
        init_std=cfg.get("initializer_range", 0.02),
        remat=cfg["train"].get("remat", True),
    )


def build_train(cfg, traffic, step_options, mesh):
    """``(step, tx)``: the user's call, ``hvd.make_train_step`` over the
    flax model with its defaults; the loss is the model's own."""
    import horovod_tpu.jax as hvd
    from horovod_tpu.models.ling import LingLM, lm_loss

    model = LingLM(model_config(cfg))
    loss_fn = lambda p, batch: lm_loss(model, p, batch)
    tx = hvd.DistributedOptimizer(optimizer(cfg))
    return hvd.make_train_step(loss_fn, tx, mesh, **step_options), tx
