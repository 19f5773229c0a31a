"""``models/recompute.remat_layer``: what a recomputed layer keeps.

The five language models wrap their layer in it. The forward flash kernel's
result and logsumexp are named residuals (``ops/pallas_attention
.FLASH_RESIDUALS``) and the helper's policy keeps them, so a step holds ONE
forward flash kernel an attention layer where plain ``nn.remat`` holds two
(first pass and recomputation), and every gradient is the same bits: the kept
arrays are what the second pass would have written again. Small models, two
layers, one row; counted in the step lowered for the chip, compared
interpreted on the CPU under ``test_flash_attention.AS_STATED``."""

import dataclasses
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

import test_keye_vl
import test_lfm2_moe
import test_qwen3_next
import test_xing4
from benchmark.weights import is_leaf
from test_flash_attention import AS_STATED
from horovod_tpu import trace as hvd_trace
from horovod_tpu.models import recompute, transformer
from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.sparse_index import KL_RESIDUALS

T, VOCAB = 64, 251


def _cross_entropy(model):
    def loss(p, tokens, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply({"params": p}, tokens), labels).mean()
    return loss


def _draw(shapes, kind):
    """Seeded float32 weights by numpy (no program is compiled to make them):
    ``kind(path, leaf)`` says ``"normal"`` (at the leaf's ``std``, or 0.02),
    ``"ones"`` or ``"zeros"``."""
    rng = np.random.default_rng(11)
    make = {"normal": lambda leaf: getattr(leaf, "std", 0.02)
            * rng.standard_normal(leaf.shape),
            "ones": lambda leaf: np.ones(leaf.shape),
            "zeros": lambda leaf: np.zeros(leaf.shape)}
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.asarray(make[kind(path, leaf)](leaf),
                                       jnp.float32),
        shapes, is_leaf=is_leaf)


def _gpt():
    model = transformer.TransformerLM(
        vocab_size=VOCAB, d_model=64, n_heads=4, n_layers=2, max_len=T,
        remat=True)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32))["params"])
    params = _draw(shapes, lambda path, leaf: "ones" if "scale" in
                   jax.tree_util.keystr(path) else "normal")
    return transformer, _cross_entropy(model), params, T, 2, "_fwd_kernel"


def _hybrid(test, lm, **over):
    """One of the four expert models at its own test file's small widths,
    cut to two layers."""
    cfg = {**test.CFG, "num_hidden_layers": 2, **over}
    model = lm(dataclasses.replace(test.family.model_config(cfg),
                                   dtype=jnp.bfloat16))
    assert model.cfg.remat
    return model, _draw(test.family.param_spec(cfg),
                        lambda path, leaf: leaf.kind)


def _qwen3_next():
    model, params = _hybrid(test_qwen3_next, test_qwen3_next.qn.Qwen3NextLM,
                            full_attention_interval=2)
    return test_qwen3_next.qn, _cross_entropy(model), params, T, 1, \
        "_fwd_kernel"


def _lfm2_moe():
    model, params = _hybrid(test_lfm2_moe, test_lfm2_moe.lm.Lfm2MoeLM,
                            layer_types=["conv", "full_attention"])
    return test_lfm2_moe.lm, _cross_entropy(model), params, T, 1, \
        "_fwd_kernel"


def _xing4():
    model, params = _hybrid(test_xing4, test_xing4.xm.Xing4LM,
                            hc_sinkhorn_iters=2)
    return test_xing4.xm, _cross_entropy(model), params, T, 2, "_fwd_kernel"


def _keye_vl():
    # 128 positions: one tile of the selection's and the objective's kernels
    km = test_keye_vl.km
    model, params = _hybrid(test_keye_vl, km.KeyeVLLM)
    return (km, lambda p, tokens, labels: km.lm_loss(model, p,
                                                     (tokens, labels)),
            params, 128, 2, "_fwd_kernel_sel")


@pytest.mark.parametrize(
    "case", [_gpt, _qwen3_next, _lfm2_moe, _xing4, _keye_vl],
    ids=lambda f: f.__name__.strip("_"))
def test_a_model_runs_the_forward_flash_kernel_once_a_layer(monkeypatch, case):
    """Each model file asks ``remat_layer`` for its recomputation: lowered for
    the chip the step counts one forward flash kernel an attention layer, and
    its gradients are plain ``nn.remat``'s to the bit."""
    module, loss, params, t, attention_layers, kernel = case()
    rng = np.random.default_rng(11)
    tokens, labels = (jnp.asarray(rng.integers(0, VOCAB, (1, t)), jnp.int32)
                      for _ in range(2))

    def both(p):
        kept = jax.grad(loss)(p, tokens, labels)
        with monkeypatch.context() as m:
            m.setattr(module, "remat_layer", nn.remat)
            return kept, jax.grad(loss)(p, tokens, labels)

    # one program holds both gradients (what the two share is computed once)
    hvd_trace.reset_build_ledger()
    kept, plain = jax.jit(both).lower(params).compile(
        compiler_options=AS_STATED)(params)
    assert hvd_trace.plan_args()["layer_recompute_keeps"] == recompute.KEEPS
    jax.tree.map(np.testing.assert_array_equal, kept, plain)
    assert any(float(jnp.abs(g.astype(jnp.float32)).max()) > 0
               for g in jax.tree.leaves(kept))

    monkeypatch.setattr(pa, "_resolve_interpret", lambda interpret: False)
    text = jax.jit(jax.grad(loss)).trace(params, tokens, labels).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = Counter(re.findall(r'kernel_name = "(\w+)"', text))
    assert calls[kernel] == attention_layers, calls


def test_the_helper_keeps_every_name_an_op_declares():
    """One place holds the policy: the names are the ops' own constants, and
    no model file builds a recomputation of its own."""
    import inspect

    from horovod_tpu.models import keye_vl, lfm2_moe, qwen3_next, xing4

    assert recompute.KEEPS == (*pa.FLASH_RESIDUALS, KL_RESIDUALS)
    for module in (transformer, qwen3_next, lfm2_moe, xing4, keye_vl):
        source = inspect.getsource(module)
        assert "remat_layer(" in source
        assert "nn.remat" not in source and "save_only" not in source
