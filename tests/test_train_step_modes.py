"""The one step body of ``make_train_step`` under each mode's data: plain,
``zero1=True`` and ``rules="gpt"``, post-hoc and streamed (``overlap``).
In every mode the non-finite guard holds params and state on every rank,
the eagerly built step is the jitted function itself, and ``has_aux``
returns aux averaged beside ``abort`` with no flag."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
import horovod_tpu.jax as hvdj
from horovod_tpu.models.transformer import TransformerLM, make_gpt_loss_fn
from horovod_tpu.parallel.mesh import build_mesh

DIM, VOCAB, HEADS, T = 8, 32, 2, 8
GLOBAL_B = 8
TX = optax.adamw(1e-2)
_GPT_LOSS = make_gpt_loss_fn(HEADS, model_axis="model", dtype=jnp.float32)


def _mlp_params():
    rng = np.random.RandomState(0)
    return {
        f"layer{i}": {"w": jnp.asarray(rng.randn(DIM, DIM), jnp.float32) * 0.3,
                      "b": jnp.zeros((DIM,), jnp.float32)}
        for i in range(2)
    }


def _mlp_loss(p, batch):
    x, y, scale = batch
    h = x
    for i in range(2):
        h = jnp.tanh(h @ p[f"layer{i}"]["w"] + p[f"layer{i}"]["b"])
    return jnp.mean((h - y) ** 2) * jnp.mean(scale)


def _gpt_params():
    model = TransformerLM(vocab_size=VOCAB, d_model=16, n_heads=HEADS,
                          n_layers=1, max_len=T)
    return model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)
    )["params"]


def _gpt_loss(p, batch):
    tokens, labels, scale = batch
    return _GPT_LOSS(p, (tokens, labels)) * jnp.mean(scale)


def _with_aux(loss_fn):
    def f(p, batch):
        return loss_fn(p, batch), {"scale": jnp.mean(batch[-1])}
    return f


class Mode:
    """One column of the mode table: mesh, loss, params, state and the
    keywords that select the mode."""

    def __init__(self, name, devices):
        self.name = name
        rng = np.random.RandomState(1)
        if name == "composed":
            self.mesh = build_mesh({"data": 2, "model": 2},
                                   devices=devices[:4])
            self.n_data, self.loss, self.kw = 2, _gpt_loss, {"rules": "gpt"}
            self.params = _gpt_params()
            self.inputs = (
                jnp.asarray(rng.randint(0, VOCAB, (GLOBAL_B, T)), jnp.int32),
                jnp.asarray(rng.randint(0, VOCAB, (GLOBAL_B, T)), jnp.int32),
            )
        else:
            self.mesh = build_mesh({"data": 4}, devices=devices[:4])
            self.n_data, self.loss = 4, _mlp_loss
            self.kw = {"zero1": True} if name == "zero1" else {}
            self.params = _mlp_params()
            self.inputs = (
                jnp.asarray(rng.randn(GLOBAL_B, DIM), jnp.float32),
                jnp.asarray(rng.randn(GLOBAL_B, DIM), jnp.float32),
            )
        self.state = (
            hvdj.init_zero1_stream_state(TX, self.params, 4)
            if name == "zero1" else TX.init(self.params)
        )
        self.eager = name != "composed"

    def step(self, overlap, **kw):
        return hvdj.make_train_step(
            kw.pop("loss", self.loss), TX, self.mesh, overlap=overlap,
            donate=False, tuned=False, **self.kw, **kw,
        )

    def batch(self, scale):
        return self.inputs + (jnp.asarray(scale, jnp.float32),)

    def clean(self):
        return self.batch(np.ones(GLOBAL_B))

    def poisoned(self):
        """The LAST data rank's rows alone are not finite."""
        scale = np.ones(GLOBAL_B)
        scale[-(GLOBAL_B // self.n_data):] = np.nan
        return self.batch(scale)


CASES = [(m, o) for m in ("plain", "zero1", "composed") for o in (False, True)]
IDS = [f"{m}-{'overlap' if o else 'posthoc'}" for m, o in CASES]
modes = pytest.mark.parametrize("mode,overlap", CASES, ids=IDS)


def _every_shard(tree):
    """Every rank's copy of every leaf, as numpy."""
    return [
        np.asarray(s.data)
        for leaf in jax.tree.leaves(tree)
        for s in leaf.addressable_shards
    ]


def _assert_same_on_every_rank(got, want):
    got, want = _every_shard(got), _every_shard(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def _moved(got, want):
    return any(
        not np.array_equal(g, w)
        for g, w in zip(_every_shard(got), _every_shard(want))
    )


def _placed(step, mode):
    """Params and state as the step itself lays them out (one clean call),
    so that a later comparison is shard against shard."""
    params, state, _ = step(mode.params, mode.state, mode.clean())[:3]
    return params, state


@modes
def test_skip_holds_params_and_state_on_every_rank(mode, overlap, devices):
    m = Mode(mode, devices)
    step = m.step(overlap, nonfinite="skip")
    params, state = _placed(step, m)
    assert _moved(params, m.params)  # a clean step still updates
    held_p, held_s, _ = step(params, state, m.poisoned())
    _assert_same_on_every_rank(held_p, params)
    _assert_same_on_every_rank(held_s, state)
    next_p, next_s, loss = step(held_p, held_s, m.clean())
    assert np.isfinite(float(loss))
    assert _moved(next_p, held_p) and _moved(next_s, held_s)


@modes
def test_abort_raises_and_leaves_params_and_state(mode, overlap, devices):
    m = Mode(mode, devices)
    step = m.step(overlap, nonfinite="abort")
    params, state = _placed(step, m)
    before_p, before_s = _every_shard(params), _every_shard(state)
    with pytest.raises(hvd.HorovodInternalError) as e:
        step(params, state, m.poisoned())
    assert "non-finite gradient guard (policy abort)" in str(e.value)
    assert "was not applied on any rank" in str(e.value)
    for got, want in zip(_every_shard(params) + _every_shard(state),
                         before_p + before_s):
        np.testing.assert_array_equal(got, want)
    out = step(params, state, m.clean())  # and a clean step goes through
    assert len(out) == 3 and np.isfinite(float(out[2]))
    assert _moved(out[0], params)


@modes
def test_untraced_step_is_the_jitted_function(mode, overlap, devices):
    from horovod_tpu import trace as hvd_trace

    assert not hvd_trace.ACTIVE
    m = Mode(mode, devices)
    step = m.step(overlap)
    jitted_type = type(jax.jit(lambda x: x))
    if m.eager:
        # built at the call: nothing stands between the caller and the jit
        assert isinstance(step, jitted_type)
        assert not hasattr(step, "__hvd_trace_wrapped__")
        assert "module @jit_step " in step.lower(
            m.params, m.state, m.clean()
        ).as_text()
    else:
        # rules are matched against the live trees on the first call
        assert step.jitted is None and step.sharding_specs is None
        step(m.params, m.state, m.clean())
        assert isinstance(step.jitted, jitted_type)
        assert set(step.sharding_specs) == {"params", "opt_state"}


@modes
def test_has_aux_with_abort_returns_aux_averaged_and_no_flag(
        mode, overlap, devices):
    m = Mode(mode, devices)
    step = m.step(overlap, nonfinite="abort", has_aux=True,
                  loss=_with_aux(m.loss))
    plain = m.step(overlap, nonfinite="abort")
    # a different scale on every data rank: the average is over ranks
    scale = np.repeat(np.arange(1.0, m.n_data + 1), GLOBAL_B // m.n_data)
    out = step(m.params, m.state, m.batch(scale))
    assert len(out) == 4  # params, state, loss, aux: the flag stays inside
    np.testing.assert_allclose(
        float(out[3]["scale"]), scale.mean(), rtol=1e-6
    )
    for s in out[3]["scale"].addressable_shards:
        np.testing.assert_allclose(np.asarray(s.data), scale.mean(),
                                   rtol=1e-6)
    ref = plain(m.params, m.state, m.batch(scale))
    np.testing.assert_allclose(float(out[2]), float(ref[2]), rtol=1e-6)
    _assert_same_on_every_rank(out[0], ref[0])
    with pytest.raises(hvd.HorovodInternalError):
        step(m.params, m.state, m.poisoned())
