"""Nemotron-H-style hybrid decoder (``horovod_tpu/models/nemotron_h.py``): every
layer ONE sublayer behind one norm and one residual add, in the order
``hybrid_override_pattern`` gives: ``M`` a Mamba-2 state-space mixer, ``*``
grouped-query attention without positions, ``E`` a dropless sigmoid top-k
layer of ungated ``relu(.)^2`` experts, of which this chip holds a share,
beside a shared expert; untied head.

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

REFERENCE = "nemotron_h"
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def dims(cfg):
    m = dict(
        d=cfg["hidden_size"], V=cfg["vocab_size"],
        kinds=cfg["hybrid_override_pattern"],
        H=cfg["mamba_num_heads"], P=cfg["mamba_head_dim"],
        N=cfg["ssm_state_size"], G=cfg["n_groups"], taps=cfg["conv_kernel"],
        h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"],
        E=cfg["n_routed_experts_routed"], held=cfg["n_routed_experts"],
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        fs=cfg["moe_shared_expert_intermediate_size"],
    )
    if len(m["kinds"]) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not list "
                         "num_hidden_layers layers")
    m["L"] = len(m["kinds"])
    m["inner"], m["bc"] = m["H"] * m["P"], m["G"] * m["N"]
    m["mamba_layers"] = m["kinds"].count(MAMBA)
    m["attn_layers"] = m["kinds"].count(ATTENTION)
    m["expert_layers"] = m["kinds"].count(EXPERTS)
    return m


def param_spec(cfg):
    """The NemotronHLM parameter tree, leaf for leaf."""
    m = dims(cfg)
    d, std = m["d"], cfg.get("initializer_range", 0.02)
    w = lambda *shape: Leaf(tuple(shape), "normal", std)
    zeros = lambda *shape: Leaf(tuple(shape), "zeros")
    ones = lambda *shape: Leaf(tuple(shape), "ones")
    kernel = lambda *shape: {"kernel": w(*shape)}
    norm = lambda n: {"scale": ones(n)}
    # the experts' second matrices alone are drawn narrower
    # (``seeded_expert_down_std``: the configuration's file says why)
    down = lambda *shape: Leaf(tuple(shape), "normal",
                               cfg.get("seeded_expert_down_std", std))
    spec = {"embed_tokens": {"embedding": w(m["V"], d)}, "norm_f": norm(d),
            "lm_head": kernel(d, m["V"])}
    for i, kind in enumerate(m["kinds"]):
        if kind == MAMBA:
            mixer = {
                "in_proj": kernel(d, 2 * m["inner"] + 2 * m["bc"] + m["H"]),
                "conv": {"kernel": w(m["taps"], m["inner"] + 2 * m["bc"]),
                         "bias": zeros(m["inner"] + 2 * m["bc"])},
                "A_log": zeros(m["H"]), "dt_bias": zeros(m["H"]),
                "D": ones(m["H"]), "norm": norm(m["inner"]),
                "out_proj": kernel(m["inner"], d),
            }
        elif kind == ATTENTION:
            mixer = {
                "q_proj": kernel(d, m["h"] * m["hd"]),
                "k_proj": kernel(d, m["kv"] * m["hd"]),
                "v_proj": kernel(d, m["kv"] * m["hd"]),
                "out_proj": kernel(m["h"] * m["hd"], d),
            }
        else:
            mixer = {
                "router": kernel(d, m["E"]),
                "expert_bias": Leaf((m["E"],), "normal",
                                    cfg["expert_bias_std"]),
                "experts": {"up": w(m["held"], d, m["f"]),
                            "down": down(m["held"], m["f"], d)},
                "shared_up_proj": kernel(d, m["fs"]),
                "shared_down_proj": {"kernel": down(m["fs"], d)},
            }
        spec[f"layer_{i}"] = {"norm": norm(d), "mixer": mixer}
    return spec


def expected_held_per_token(cfg) -> float:
    """Of a token's ``k`` chosen experts, how many are held here when the
    choice is uniform over all of them (seeded weights route so)."""
    m = dims(cfg)
    return m["k"] * m["held"] / m["E"]


def matmul_params_per_token(cfg) -> float:
    """Weights a token is multiplied by: a Mamba-2 layer's two projections,
    an attention layer's four, an expert layer's router, shared expert and
    the expected held experts it is routed to (two matrices each), and the
    head. The lookup is a gather; the taps, the scan and the norms are
    counted apart or not at all."""
    m = dims(cfg)
    d = m["d"]
    mamba = d * (2 * m["inner"] + 2 * m["bc"] + m["H"]) + m["inner"] * d
    attn = 2 * d * m["h"] * m["hd"] + 2 * d * m["kv"] * m["hd"]
    sparse = (d * m["E"] + 2 * d * m["fs"]
              + expected_held_per_token(cfg) * 2 * d * m["f"])
    return (m["mamba_layers"] * mamba + m["attn_layers"] * attn
            + m["expert_layers"] * sparse + d * m["V"])


def ssd_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward selective scans of one step
    on one chip (all Mamba-2 layers), by the per-token recurrence, whatever
    implements it: per token and head the decay of the state (P N), the
    rank-one update and S C (2 P N each), ``5 P N``, and ``D x`` (2 P).
    Bytes: x, B and C (a group's, read once) read and y written once in
    bf16, dt read in f32; the state stays on the chip."""
    m = dims(cfg)
    tokens = batch_per_chip * traffic["seq_len"]
    ops = m["mamba_layers"] * tokens * m["H"] * (
        5.0 * m["P"] * m["N"] + 2 * m["P"])
    bytes_ = m["mamba_layers"] * tokens * (
        (2 * m["inner"] + 2 * m["bc"]) * 2 + m["H"] * 4)
    return ops, bytes_


def train_ops_per_step(cfg, traffic, batch_per_chip) -> float:
    """Required operations of one optimizer step on one chip: 6 per multiplied
    weight per token (the expected held experts a token among them, two
    matrices each), causal attention at the attention layers (2 * 2 * T^2 / 2
    * h * hd forward, times three with the backward), and the scans by their
    per-token rule (``ssd_fwd_cost``'s operations, times three)."""
    m = dims(cfg)
    T = traffic["seq_len"]
    attn = 6.0 * m["attn_layers"] * batch_per_chip * T * T * m["h"] * m["hd"]
    scan = 3.0 * ssd_fwd_cost(cfg, traffic, batch_per_chip)[0]
    return (6.0 * matmul_params_per_token(cfg) * batch_per_chip * T
            + attn + scan)


def attn_fwd_calls(cfg) -> int:
    """The forward flash kernel's calls in ONE forward pass, one an attention
    layer: what ``attn_fwd_cost`` is the least cost of. The layer's
    recomputation keeps the kernel's result, so a step makes no more."""
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


def moe_experts_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward grouped products of one step
    on one chip (the expert layers): the expected (token, expert) pairs held
    here times the TWO matrices of an expert without a gate (4 * d * f
    operations a pair); the held experts' weights read once in bf16, each
    pair's row read (d) and written (d) in bf16."""
    m = dims(cfg)
    tokens = batch_per_chip * traffic["seq_len"]
    pairs = tokens * expected_held_per_token(cfg)
    ops = m["expert_layers"] * pairs * 4.0 * m["d"] * m["f"]
    bytes_ = m["expert_layers"] * (m["held"] * 2 * m["d"] * m["f"] * 2
                                   + pairs * 2 * m["d"] * 2)
    return ops, bytes_


def model_config(cfg):
    """The configuration file's keys as ``NemotronHConfig``'s."""
    from horovod_tpu.models.nemotron_h import NemotronHConfig

    m = dims(cfg)
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 or \
            cfg["n_shared_experts"] != 1:
        raise ValueError("nemotron_h routes without a group limit beside one "
                         "shared expert; the configuration asks for another")
    return NemotronHConfig(
        vocab_size=m["V"], pattern=m["kinds"], d_model=m["d"],
        mamba_heads=m["H"], mamba_head_dim=m["P"], ssm_state=m["N"],
        ssm_groups=m["G"], conv_kernel=m["taps"], chunk=cfg["chunk_size"],
        n_heads=m["h"], n_kv_heads=m["kv"], head_dim=m["hd"],
        n_experts=m["E"], experts_held=m["held"],
        first_expert=cfg.get("first_expert_held", 0), top_k=m["k"],
        expert_dim=m["f"], shared_dim=m["fs"],
        norm_topk=cfg["norm_topk_prob"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        eps=cfg["layer_norm_epsilon"],
        init_std=cfg.get("initializer_range", 0.02),
        remat=cfg["train"].get("remat", True),
    )


def build_train(cfg, traffic, step_options, mesh):
    """``(step, tx)``: the user's call, ``hvd.make_train_step`` over the
    flax model with its defaults; the loss is the model's own."""
    import horovod_tpu.jax as hvd
    from horovod_tpu.models.nemotron_h import NemotronHLM, lm_loss

    model = NemotronHLM(model_config(cfg))
    loss_fn = lambda p, batch: lm_loss(model, p, batch)
    tx = hvd.DistributedOptimizer(optimizer(cfg))
    return hvd.make_train_step(loss_fn, tx, mesh, **step_options), tx
