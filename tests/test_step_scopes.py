"""Scopes inside the compiled step (docs/timeline.md "Scopes in the compiled
step"): every builder of ``make_train_step`` names the loss and its gradient,
the exchange's packing, collective and unpacking, the optimizer update, the
non-finite guard and the flash backward by the one vocabulary
``trace.STEP_SCOPES``, which ``benchmark/scope_groups/`` groups a step's device
time by. Lowered on a two-device CPU mesh at d128/L2; nothing runs but the
composed builder's first call."""

import re

import jax
import jax.numpy as jnp
import optax
import pytest

import horovod_tpu.jax as hvd
from horovod_tpu import trace as hvd_trace
from horovod_tpu.models.transformer import TransformerLM, make_gpt_loss_fn

VOCAB, D, HEADS, LAYERS, T = 256, 128, 4, 2, 256
MODEL = TransformerLM(vocab_size=VOCAB, d_model=D, n_heads=HEADS,
                      n_layers=LAYERS, max_len=T)
TOKENS = jnp.zeros((4, T), jnp.int32)
TX = optax.adamw(1e-3)


def _loss(p, batch):
    tokens, labels = batch
    logits = MODEL.apply({"params": p}, tokens)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels
    ).mean()


def _params():
    return MODEL.init(jax.random.PRNGKey(0), TOKENS)["params"]


def _lower_plain(devices, optimizer=TX):
    mesh = hvd.build_mesh({"data": 2}, devices=devices[:2])
    step = hvd.make_train_step(_loss, optimizer, mesh, nonfinite="skip")
    params = jax.eval_shape(_params)
    state = jax.eval_shape(optimizer.init, params)
    return step.lower(params, state, (TOKENS, TOKENS))


def _lower_zero1(devices):
    mesh = hvd.build_mesh({"data": 2}, devices=devices[:2])
    step = hvd.make_train_step(_loss, TX, mesh, zero1=True,
                               nonfinite="skip")
    params = jax.eval_shape(_params)
    state = jax.eval_shape(
        lambda p: hvd.init_zero1_stream_state(TX, p, 2), params
    )
    return step.lower(params, state, (TOKENS, TOKENS))


def _lower_composed(devices):
    mesh = hvd.build_mesh({"data": 1, "model": 2}, devices=devices[:2])
    step = hvd.make_train_step(
        make_gpt_loss_fn(HEADS, model_axis="model"), TX, mesh, rules="gpt",
        nonfinite="skip", donate=False,
    )
    params = _params()
    state = TX.init(params)
    step(params, state, (TOKENS, TOKENS))  # the composed step builds lazily
    return step.jitted.lower(params, state, (TOKENS, TOKENS))


def _paths(lowered):
    """Every scope path the lowering names an operation by."""
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("lower", [_lower_plain, _lower_zero1,
                                   _lower_composed],
                         ids=["plain", "zero1", "composed"])
def test_every_builder_names_the_whole_vocabulary(lower, devices):
    lowered = lower(devices)
    # the benchmark finds the launch as ^jit_step\(
    assert "module @jit_step " in lowered.as_text()
    paths = _paths(lowered)
    for scope in hvd_trace.STEP_SCOPES:
        assert any(scope in p for p in paths), scope
    # flax's module scopes nest under the loss scope, forward and backward
    loss = hvd_trace.SCOPE_LOSS_GRAD
    assert any(p.startswith(loss + "/jvp(") for p in paths)
    assert any(p.startswith(loss + "/transpose(jvp(") for p in paths)
    # the backward kernels are told from the forward's by their scope
    # (the CPU's interpreter unrolls a kernel into loops beneath it)
    assert any(re.search(hvd_trace.SCOPE_FLASH_BWD + r"\)?/pallas_call$", p)
               for p in paths)
    # the collective sits under the exchange's reduce child, and nowhere
    # is an exchange opened inside an exchange
    assert any(re.search(hvd_trace.SCOPE_EXCHANGE_REDUCE
                         + r"/(psum|reduce_scatter|all_gather)", p)
               for p in paths)
    assert not any(p.count(hvd_trace.SCOPE_EXCHANGE) > 1 for p in paths)


def _exchange_parents(paths):
    ex = hvd_trace.SCOPE_EXCHANGE
    return {p.split(ex)[0] for p in paths if ex in p}


def _all_reduces(lowered):
    return len(re.findall(r"stablehlo\.all_reduce", lowered.as_text()))


def test_distributed_optimizer_adds_no_second_reduction(devices):
    """``make_train_step`` reduces the gradients, and a
    ``DistributedOptimizer`` passed to it is opened, not called: with and
    without the wrapper the exchange has the one parent (the step) and
    the program holds as many all-reduces."""
    bare = _lower_plain(devices)
    wrapped = _lower_plain(devices, hvd.DistributedOptimizer(TX))
    assert _exchange_parents(_paths(bare)) == {""}
    assert _exchange_parents(_paths(wrapped)) == {""}
    assert _all_reduces(wrapped) == _all_reduces(bare) > 0
