"""Keye-VL-2.0-style language model (``horovod_tpu/models/keye_vl.py``):
every layer grouped-query attention behind a learned top-k choice of keys (an
indexer of small heads over ONE key head, the selection inside the flash
kernels, an objective of the indexer's own) and a dropless softmax top-k
expert layer, of which this chip holds a share, with no shared expert;
untied head.

The benchmark makes the weights (``param_spec`` + ``weights.make_params``); the
program supplies the model and ``hvd.make_train_step``. The counts below are
the required operations and bytes of the configuration's mathematics at the
cell's shapes, the LEAST work whatever form the program gives the selection:
attention and the objective over the SELECTED pairs, the indexer's scores over
the causal ones. Nothing here reads the program.
"""

from __future__ import annotations

from ..weights import Leaf
# the same AdamW and state as the other families; uniform tokens over the slice
from .gpt_dense import first_gradient, optimizer
from .qwen3_next import make_batches

REFERENCE = "keye_vl"


def dims(cfg):
    sa = cfg["sa_config"]
    return dict(
        d=cfg["hidden_size"], V=cfg["vocab_size"],
        L=cfg["num_hidden_layers"], h=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        ih=sa["indexer_num_heads"], id=sa["indexer_head_dim"],
        topk=sa["topk"], E=cfg["num_experts_routed"],
        held=cfg["num_experts"], k=cfg["num_experts_per_tok"],
        f=cfg["moe_intermediate_size"],
    )


def param_spec(cfg):
    """The KeyeVLLM parameter tree, leaf for leaf."""
    m = dims(cfg)
    d, std = m["d"], cfg.get("initializer_range", 0.02)
    w = lambda *shape: Leaf(tuple(shape), "normal", std)
    kernel = lambda *shape: {"kernel": w(*shape)}
    norm = lambda n: {"scale": Leaf((n,), "ones")}
    # the table alone is drawn wider (``seeded_embedding_std``: the
    # configuration's file says why)
    table = Leaf((m["V"], d), "normal", cfg.get("seeded_embedding_std", std))
    spec = {"embed_tokens": {"embedding": table}, "norm": norm(d),
            "lm_head": kernel(d, m["V"])}
    for i in range(m["L"]):
        spec[f"layer_{i}"] = {
            "input_layernorm": norm(d), "post_attention_layernorm": norm(d),
            "self_attn": {
                "q_proj": kernel(d, m["h"] * m["hd"]),
                "k_proj": kernel(d, m["kv"] * m["hd"]),
                "v_proj": kernel(d, m["kv"] * m["hd"]),
                "o_proj": kernel(m["h"] * m["hd"], d),
                "q_norm": norm(m["hd"]), "k_norm": norm(m["hd"]),
                "indexer": {
                    "wq": kernel(d, m["ih"] * m["id"]),
                    "wk": kernel(d, m["id"]),
                    "k_norm": {"scale": Leaf((m["id"],), "ones"),
                               "bias": Leaf((m["id"],), "zeros")},
                    "weights_proj": kernel(d, m["ih"]),
                },
            },
            "mlp": {
                "router": kernel(d, m["E"]),
                "experts": {"gate": w(m["held"], d, m["f"]),
                            "up": w(m["held"], d, m["f"]),
                            "down": w(m["held"], m["f"], d)},
            },
        }
    return spec


def selected_pairs(cfg, traffic) -> int:
    """(query, key) pairs a sequence's selection holds: ``sum_t min(t + 1,
    topk)``."""
    T, k = traffic["seq_len"], dims(cfg)["topk"]
    k = min(k, T)
    return k * (k + 1) // 2 + (T - k) * k


def causal_pairs(traffic) -> int:
    T = traffic["seq_len"]
    return T * (T + 1) // 2


def expected_held_per_token(cfg) -> float:
    """Of a token's ``k`` chosen experts, how many are held here when the
    choice is uniform over all of them (seeded weights route so)."""
    m = dims(cfg)
    return m["k"] * m["held"] / m["E"]


def indexer_params(cfg) -> int:
    """The indexer's three matrices a layer."""
    m = dims(cfg)
    return m["d"] * (m["ih"] * m["id"] + m["id"] + m["ih"])


def matmul_params_per_token(cfg) -> float:
    """Weights a token is multiplied by: every layer's four attention
    matrices, the indexer's three, the router and the expected held experts
    it is routed to, and the head. The lookup is a gather; norms and rotary
    are not counted."""
    m = dims(cfg)
    d = m["d"]
    attn = 2 * d * m["h"] * m["hd"] + 2 * d * m["kv"] * m["hd"]
    sparse = d * m["E"] + expected_held_per_token(cfg) * 3 * d * m["f"]
    return m["L"] * (attn + indexer_params(cfg) + sparse) + d * m["V"]


def attn_fwd_calls(cfg) -> int:
    """The forward flash kernel's calls in ONE forward pass, one a layer:
    what ``attn_fwd_cost`` is the least cost of. With recomputation on a step
    runs the pass twice, and ``attn_fwd_roofline`` holds one pass's bound
    against one pass's share of the kernel's time."""
    return dims(cfg)["L"]


def attn_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the forward flash kernel calls of one
    step on one chip (all layers): QK^T and PV over the SELECTED pairs and
    the query heads (a masked walk over every causal block does 4.3 times
    that at 16384 positions; the share then says so); q, k, v (as the kernel
    is fed them: the key/value heads repeated to the query heads) read and the
    output written once in bf16."""
    m = dims(cfg)
    T = traffic["seq_len"]
    width = m["h"] * m["hd"]
    pairs = batch_per_chip * selected_pairs(cfg, traffic)
    ops = 4.0 * m["L"] * pairs * width
    bytes_ = 4.0 * m["L"] * batch_per_chip * T * width * 2
    return ops, bytes_


def sparse_index_calls(cfg) -> int:
    """The selection kernel's calls in ONE forward pass, one a layer (with
    recomputation on a step runs the pass twice)."""
    return dims(cfg)["L"]


def sparse_index_select_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of one forward pass's selections on one
    chip (all layers), without the projections: over the CAUSAL pairs the
    indexer heads' products (``2 * heads * head_dim`` a pair; the rectifier,
    the weighted sum and the search for the k-th value are not counted); qI,
    kI and w read once and the selection written once at its least, an index
    of two bytes a selected pair. Compute-bound by two orders."""
    m = dims(cfg)
    T = traffic["seq_len"]
    heads = m["ih"] * m["id"]
    ops = 2.0 * m["L"] * batch_per_chip * causal_pairs(traffic) * heads
    bytes_ = m["L"] * batch_per_chip * (
        T * ((heads + m["id"]) * 2 + m["ih"] * 4)
        + 2 * selected_pairs(cfg, traffic))
    return ops, bytes_


def sparse_index_fwd_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of one forward of the indexer and the
    selection on one chip (all layers): the three projections (the layer's
    input read, qI, kI and w written) and the selections
    (``sparse_index_select_cost``)."""
    m = dims(cfg)
    T = traffic["seq_len"]
    ops, bytes_ = sparse_index_select_cost(cfg, traffic, batch_per_chip)
    rows = m["L"] * batch_per_chip * T
    return (ops + 2.0 * rows * indexer_params(cfg),
            bytes_ + rows * (m["d"] * 2 + (m["ih"] * m["id"] + m["id"]) * 2
                             + m["ih"] * 4))


def sparse_index_kl_cost(cfg, traffic, batch_per_chip):
    """Least operations and bytes of the indexer's objective WITH its
    gradient on one chip (all layers), over the SELECTED pairs: the heads'
    QK^T once more for the target (``2 * heads * head_dim`` a pair), the
    indexer's scores once more and their two transposed products (``3 * 2 *
    indexer heads * head_dim``); q, k (the key/value heads as they are), qI
    and kI read, qI's gradient written in bf16, kI's and w's in float32, and
    two bytes a selected pair for the selection. Compute-bound."""
    m = dims(cfg)
    T = traffic["seq_len"]
    heads = m["ih"] * m["id"]
    pairs = m["L"] * batch_per_chip * selected_pairs(cfg, traffic)
    ops = pairs * (2.0 * m["h"] * m["hd"] + 6.0 * heads)
    bytes_ = 2.0 * pairs + m["L"] * batch_per_chip * T * (
        (m["h"] + m["kv"]) * m["hd"] * 2 + m["h"] * 4
        + 2 * heads * 2 + m["id"] * (2 + 4) + m["ih"] * 8)
    return ops, bytes_


def train_ops_per_step(cfg, traffic, batch_per_chip) -> float:
    """Required operations of one optimizer step on one chip: 6 per multiplied
    weight per token (the indexer's matrices and the expected held experts a
    token among them); attention over the selected pairs, forward times three
    with the backward; the indexer's scores over the causal pairs (forward
    only: no gradient passes through the choice); and the objective over the
    selected pairs: the heads' QK^T once more for its target (``2 * heads *
    head_dim`` a pair), the indexer's scores once more and their two
    transposed products (``3 * 2 * indexer heads * head_dim``). Recomputed
    work is not in it."""
    m = dims(cfg)
    T = traffic["seq_len"]
    attn, _ = attn_fwd_cost(cfg, traffic, batch_per_chip)
    scores, _ = sparse_index_select_cost(cfg, traffic, batch_per_chip)
    objective, _ = sparse_index_kl_cost(cfg, traffic, batch_per_chip)
    return (6.0 * matmul_params_per_token(cfg) * batch_per_chip * T
            + 3 * attn + scores + objective)


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


def model_config(cfg):
    """The configuration file's keys as ``KeyeVLConfig``'s."""
    from horovod_tpu.models.keye_vl import KeyeVLConfig

    m, sa = dims(cfg), cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or cfg["decoder_sparse_step"] != 1 \
            or cfg["mlp_only_layers"]:
        raise ValueError("keye_vl runs one indexer key head and an expert "
                         "layer in every layer; the configuration asks for "
                         "another")
    return KeyeVLConfig(
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"],
        n_kv_heads=m["kv"], head_dim=m["hd"],
        rope_theta=float(cfg["rope_theta"]),
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        index_heads=m["ih"], index_head_dim=m["id"], index_top_k=m["topk"],
        n_experts=m["E"], experts_held=m["held"],
        first_expert=cfg.get("first_expert_held", 0), top_k=m["k"],
        expert_dim=m["f"], norm_topk=cfg["norm_topk_prob"],
        eps=cfg["rms_norm_eps"],
        init_std=cfg.get("initializer_range", 0.02),
        remat=cfg["train"].get("remat", True),
    )


def build_train(cfg, traffic, step_options, mesh):
    """``(step, tx)``: the user's call, ``hvd.make_train_step`` over the
    flax model with its defaults; the loss is the model's own two terms."""
    import horovod_tpu.jax as hvd
    from horovod_tpu.models.keye_vl import KeyeVLLM, lm_loss

    model = KeyeVLLM(model_config(cfg))
    loss_fn = lambda p, batch: lm_loss(model, p, batch)
    tx = hvd.DistributedOptimizer(optimizer(cfg))
    return hvd.make_train_step(loss_fn, tx, mesh, **step_options), tx
