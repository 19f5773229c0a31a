"""The one step body of ``make_train_step`` under each mode's data: plain,
``zero1=True`` and ``rules="gpt"``, post-hoc and streamed (``overlap``).
In every mode the non-finite guard holds params and state on every rank,
the eagerly built step is the jitted function itself, ``has_aux`` returns
aux averaged beside ``abort`` with no flag, and a ``DistributedOptimizer``
handed to the step is opened: one exchange, under the options stated on
either side."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
import horovod_tpu.jax as hvdj
from horovod_tpu import trace as hvd_trace
from horovod_tpu.common.compression import Compression
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
    keywords that select the mode, on ``n`` devices."""

    def __init__(self, name, devices, n=4, tx=TX, **state_kw):
        self.name, self.tx = name, tx
        rng = np.random.RandomState(1)
        if name == "composed":
            self.mesh = build_mesh({"data": n // 2, "model": 2},
                                   devices=devices[:n])
            self.n_data, self.loss, self.kw = n // 2, _gpt_loss, {"rules": "gpt"}
            self.params = _gpt_params()
            self.inputs = (
                jnp.asarray(rng.randint(0, VOCAB, (GLOBAL_B, T)), jnp.int32),
                jnp.asarray(rng.randint(0, VOCAB, (GLOBAL_B, T)), jnp.int32),
            )
        else:
            self.mesh = build_mesh({"data": n}, devices=devices[:n])
            self.n_data, self.loss = n, _mlp_loss
            self.kw = {"zero1": True} if name == "zero1" else {}
            self.params = _mlp_params()
            self.inputs = (
                jnp.asarray(rng.randn(GLOBAL_B, DIM), jnp.float32),
                jnp.asarray(rng.randn(GLOBAL_B, DIM), jnp.float32),
            )
        self.state = (
            hvdj.init_zero1_stream_state(tx, self.params, n, **state_kw)
            if name == "zero1" else tx.init(self.params)
        )
        self.eager = name != "composed"

    def step(self, overlap, **kw):
        return hvdj.make_train_step(
            kw.pop("loss", self.loss), kw.pop("optimizer", self.tx),
            self.mesh, overlap=overlap, donate=False, tuned=False,
            **self.kw, **kw,
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


# ------------------------------------------ a DistributedOptimizer, opened
MODES = ("plain", "zero1", "composed")
on_modes = pytest.mark.parametrize("mode", MODES)


def _run(step, m, steps=3, state=None):
    """``steps`` calls of ``step`` from the mode's start: every shard of
    (params, state, loss) after the last."""
    params, state = m.params, m.state if state is None else state
    for _ in range(steps):
        params, state, loss = step(params, state, m.clean())
    return _every_shard((params, state, loss))


def _outcome(fn):
    """What ``fn`` gives, or the words it raises with: an option a mode
    rejects must be rejected the same whichever side stated it."""
    try:
        return "ok", fn()
    except (ValueError, TypeError) as e:
        return "raises", str(e)


def _assert_same_outcome(got, want):
    assert got[0] == want[0], (got, want)
    if got[0] == "raises":
        assert got[1] == want[1]
        return
    assert len(got[1]) == len(want[1])
    for g, w in zip(got[1], want[1]):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("n", [4, 8])
@on_modes
def test_wrapper_is_bitwise_the_optimizer_it_wraps(mode, n, devices):
    """(a) three steps with ``tx`` and with ``DistributedOptimizer(tx)``:
    params, state and loss equal to the bit, from the state the wrapper's
    own ``init`` builds where the mode takes an optax state."""
    m = Mode(mode, devices, n)
    wrapper = hvdj.DistributedOptimizer(TX)
    state = None if mode == "zero1" else wrapper.init(m.params)
    got = _run(m.step(False, optimizer=wrapper), m, state=state)
    want = _run(m.step(False), m)
    _assert_same_outcome(("ok", got), ("ok", want))


OPTIONS = {
    # stated on one side, and two values that disagree with each other
    "quantized": (True, False),
    "compression": (Compression.fp16, Compression.bf16),
    "op": (hvdj.Sum, hvdj.Adasum),
    "nonfinite": ("skip", "zero"),
}


@pytest.mark.parametrize("name", list(OPTIONS))
@on_modes
def test_wrapper_option_reaches_the_one_exchange(mode, name, devices):
    """(b) an option stated on the wrapper is the step's (the same
    program, or the same rejection, as stating it on ``make_train_step``);
    stated differently on both sides it raises by name."""
    value, other = OPTIONS[name]
    m = Mode(mode, devices,
             **({"quantized": True} if (mode, name) == ("zero1", "quantized")
                else {}))
    wrapper = hvdj.DistributedOptimizer(TX, **{name: value})
    assert wrapper.stated == {name}
    got = _outcome(lambda: _run(m.step(False, optimizer=wrapper), m))
    want = _outcome(lambda: _run(m.step(False, **{name: value}), m))
    _assert_same_outcome(got, want)
    if (mode, name) in (("plain", "quantized"), ("plain", "op"),
                        ("plain", "nonfinite"), ("zero1", "quantized")):
        assert got[0] == "ok"  # not every cell of the table is a rejection
    if got[0] == "ok":  # the same value on both sides is no disagreement
        m.step(False, optimizer=wrapper, **{name: value})
    with pytest.raises(ValueError) as e:
        m.step(False, optimizer=wrapper, **{name: other})
    assert f"make_train_step({name}={other!r})" in str(e.value)
    assert f"DistributedOptimizer({name}={value!r})" in str(e.value)


def test_wrapper_quantized_moves_the_wire_to_int8(devices):
    """(b) ``quantized=True`` said once, on the wrapper, is ONE int8 wire
    (no f32 exchange before it) with the wrapper's own ``EFState``."""
    m = Mode("plain", devices)
    wrapper = hvdj.DistributedOptimizer(TX, quantized=True)
    state = wrapper.init(m.params)
    assert isinstance(state, hvdj.EFState)
    step = m.step(False, optimizer=wrapper)
    text = step.lower(m.params, state, m.clean()).as_text()
    wire = re.findall(r"stablehlo\.(?:all_reduce|collective_permute|"
                      r"all_to_all|all_gather)[^\n]*", text)
    assert any("i8" in op for op in wire)
    # f32 crosses only as scalars or block scales, never as a gradient
    assert not any(f"tensor<{DIM}x{DIM}xf32>" in op or
                   f"tensor<{2 * (DIM * DIM + DIM)}xf32>" in op
                   for op in wire)
    out = step(m.params, state, m.clean())
    assert isinstance(out[1], hvdj.EFState)


@on_modes
def test_wrapper_backward_passes_per_step_halves_the_gradients(mode, devices):
    """(c) the wrapper's ``1 / backward_passes_per_step`` is kept: the
    update is the one the halved loss gives, to the bit."""
    sgd = optax.sgd(0.1)
    m = Mode(mode, devices, tx=sgd)
    wrapper = hvdj.DistributedOptimizer(sgd, backward_passes_per_step=2)
    args = (m.params, m.state, m.clean())
    got = m.step(False, optimizer=wrapper)(*args)
    want = m.step(False, loss=lambda p, b: 0.5 * m.loss(p, b))(*args)
    _assert_same_on_every_rank(got[:2], want[:2])
    whole = m.step(False)(*args)
    assert float(got[2]) == float(whole[2])  # the loss itself is not halved
    assert _moved(got[0], whole[0])


@on_modes
def test_wrapper_alone_in_a_shard_map_still_reduces(mode, devices):
    """(d) outside ``make_train_step`` nothing changed: in a hand-written
    ``shard_map`` over the mode's mesh the wrapper's update reduces over
    the data axis, once, under whatever scope the caller gave it."""
    from jax.sharding import PartitionSpec as P

    m = Mode("zero1" if mode == "zero1" else "plain", devices)
    if mode == "composed":
        m.mesh = build_mesh({"data": 2, "model": 2}, devices=devices[:4])
    n = m.mesh.shape["data"]
    wrapper = hvdj.DistributedOptimizer(
        TX, **({"zero1": True, "zero1_shards": n} if mode == "zero1" else {})
    )
    state = wrapper.init(m.params)
    state_spec = P("data") if mode == "zero1" else P()

    def body(params, state, batch):
        grads = jax.grad(m.loss)(params, batch)
        with jax.named_scope(hvd_trace.SCOPE_OPTIMIZER):
            updates, state = wrapper.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    fn = jax.jit(jax.shard_map(
        body, mesh=m.mesh, in_specs=(P(), state_spec, P("data")),
        out_specs=(P(), state_spec), check_vma=False,
    ))
    lowered = fn.lower(m.params, state, m.clean())
    paths = set(re.findall(r'loc\("([^"]*)"',
                           lowered.as_text(debug_info=True)))
    ex = hvd_trace.SCOPE_EXCHANGE
    assert {p.split(ex)[0] for p in paths if ex in p} == {
        hvd_trace.SCOPE_OPTIMIZER + "/"
    }
    # and what it reduces to is the step's own result
    new_params, _ = fn(m.params, state, m.clean())
    ref = Mode("zero1" if mode == "zero1" else "plain", devices, n)
    want, _, _ = ref.step(False)(ref.params, ref.state, ref.clean())
    for g, w in zip(jax.tree.leaves(new_params), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("wrapped", [False, True], ids=["bare", "wrapper"])
@on_modes
def test_step_span_says_how_many_exchanges(mode, wrapped, devices):
    """(e) with tracing armed every step span carries ``grad_exchanges``
    and which wrapper the step opened."""
    hvd_trace.reset()
    hvd_trace.install(True)
    try:
        m = Mode(mode, devices)
        optimizer = hvdj.DistributedOptimizer(TX) if wrapped else TX
        step = m.step(False, optimizer=optimizer)
        step(m.params, m.state, m.clean())
        spans = [e for e in hvd_trace.TAP.window()["events"]
                 if e["name"] == "hvd_step"]
    finally:
        hvd_trace.reset()
    assert len(spans) == 1
    assert spans[0]["args"]["grad_exchanges"] == 1
    assert spans[0]["args"]["optimizer_wrapper"] == (
        "DistributedOptimizer" if wrapped else "none"
    )


def test_zero1_wrapper_opens_to_the_zero1_step(devices):
    """``DistributedOptimizer(zero1=True, zero1_shards=n)`` handed to
    ``make_train_step`` is ``make_train_step(zero1=True)`` on the state
    its ``init`` built; a shard count that is not the mesh's raises."""
    m = Mode("zero1", devices)
    wrapper = hvdj.DistributedOptimizer(TX, zero1=True, zero1_shards=4)
    plain = Mode("plain", devices)
    got = _run(plain.step(False, optimizer=wrapper), m,
               state=wrapper.init(m.params))
    _assert_same_outcome(("ok", got), ("ok", _run(m.step(False), m)))
    with pytest.raises(ValueError, match="zero1_shards=2"):
        plain.step(False, optimizer=hvdj.DistributedOptimizer(
            TX, zero1=True, zero1_shards=2))


def test_cell_step_holds_as_many_all_reduces_as_with_the_bare_optimizer(
        devices, monkeypatch):
    """The benchmark's own ``gpt2m-train-dp4`` step (its family's
    ``build_train``, rehearsal sizes, ``data=4``): with the
    ``DistributedOptimizer`` it wraps ``adamw`` in and with bare ``adamw``
    the compiled program holds the same all-reduces."""
    from benchmark import manifest
    from benchmark.weights import make_params

    cell = manifest.Cell(manifest.load_manifest(), "gpt2m-train-dp4",
                         rehearse=True)
    mesh = build_mesh(cell.options["mesh"], devices=devices[:4])

    def all_reduces():
        step, tx = cell.family.build_train(
            cell.config, cell.traffic, cell.options["step_options"], mesh
        )
        params = jax.eval_shape(
            lambda: make_params(cell.family.param_spec(cell.config), 0)
        )
        tokens = jax.ShapeDtypeStruct(
            (4 * cell.traffic["per_chip_batch"], cell.traffic["seq_len"]),
            jnp.int32,
        )
        text = step.lower(
            params, jax.eval_shape(tx.init, params), (tokens, tokens)
        ).as_text()
        return len(re.findall(r"stablehlo\.all_reduce", text))

    wrapped = all_reduces()
    monkeypatch.setattr(hvdj, "DistributedOptimizer", lambda tx, **kw: tx)
    assert wrapped == all_reduces() > 1
