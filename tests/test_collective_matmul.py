"""Chunked collective-matmul primitives (docs/parallelism.md "Fused TP
overlap"): ring parity against the lax collectives at 2/4/8 ranks,
gradient parity through the custom VJPs, the composed fused GPT step
matching the classic step to <=5e-7, exact chunk-count-invariant wire
attribution, the symbolic plan verifier's clean sweep plus
seeded-mutation detection, and the HOROVOD_TP_* knob registry."""

import dataclasses
import itertools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvdj
from horovod_tpu.common import env as hvd_env
from horovod_tpu.ops.collective_matmul import (
    all_gather_matmul,
    expected_ppermutes,
    fusable,
    matmul_reduce_scatter,
    resolve_chunks,
    ring_hops,
)
from horovod_tpu.parallel.mesh import build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(devices, n):
    return build_mesh({"model": n}, devices=devices[:n])


# ---------------------------------------------------------------------------
# Ring shape helpers
# ---------------------------------------------------------------------------

def test_ring_hops_split():
    assert ring_hops(1) == (0, 0)
    assert ring_hops(2) == (1, 0)
    assert ring_hops(4) == (2, 1)
    assert ring_hops(8) == (4, 3)
    for n in range(2, 16):
        f, b = ring_hops(n)
        assert f + b == n - 1 and 0 <= f - b <= 1


def test_resolve_chunks_clamps_to_divisor(monkeypatch):
    monkeypatch.delenv("HOROVOD_TP_OVERLAP_CHUNKS", raising=False)
    assert resolve_chunks(8) == 1
    assert resolve_chunks(8, 3) == 2  # largest divisor <= 3
    assert resolve_chunks(8, 8) == 8
    assert resolve_chunks(4, 99) == 4  # clamped to the chunk itself
    monkeypatch.setenv("HOROVOD_TP_OVERLAP_CHUNKS", "4")
    assert resolve_chunks(8) == 4
    monkeypatch.setenv("HOROVOD_TP_OVERLAP_CHUNKS", "5")
    assert resolve_chunks(8) == 4  # 5 does not divide 8
    monkeypatch.setenv("HOROVOD_TP_OVERLAP_CHUNKS", "junk")
    assert resolve_chunks(8) == 1


def test_expected_ppermutes_and_fusable():
    assert expected_ppermutes(1) == 0
    assert expected_ppermutes(2, 1) == 1
    assert expected_ppermutes(4, 2) == 6
    assert expected_ppermutes(8, 4) == 28
    assert fusable(16, 4) and fusable(16, 2)
    assert not fusable(15, 4)
    assert not fusable(16, 1)


# ---------------------------------------------------------------------------
# Primitive parity on the virtual mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [1, 2])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_all_gather_matmul_parity(devices, n, chunks):
    mesh = _mesh(devices, n)
    t, d, f = 4 * n, 16, 24
    rng = np.random.RandomState(n)
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, f), jnp.float32)

    def body(x_loc, w_rep):
        return all_gather_matmul(x_loc, w_rep, axis_name="model",
                                 chunks=chunks)

    fn = hvdj._shard_map(
        body, mesh,
        in_specs=(P("model", None), P(None, None)),
        out_specs=P(None, None),
    )
    out = np.asarray(fn(x, w))
    np.testing.assert_allclose(out, np.asarray(x @ w),
                               rtol=2e-6, atol=2e-6)


def test_all_gather_matmul_row_order_bitwise(devices):
    """Through an identity weight the primitive IS a tiled all_gather —
    row placement must match ``lax.all_gather(..., tiled=True)``
    bitwise (x @ I adds only exact zeros)."""
    n = 4
    mesh = _mesh(devices, n)
    t, d = 4 * n, 8
    rng = np.random.RandomState(0)
    x = jnp.asarray(np.abs(rng.randn(t, d)), jnp.float32)
    eye = jnp.eye(d, dtype=jnp.float32)

    def body(x_loc, w_rep):
        fused = all_gather_matmul(x_loc, w_rep, axis_name="model",
                                  chunks=2)
        ref = lax.all_gather(x_loc, "model", axis=0, tiled=True)
        return fused, ref

    fn = hvdj._shard_map(
        body, mesh,
        in_specs=(P("model", None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
    )
    fused, ref = fn(x, eye)
    assert np.array_equal(np.asarray(fused), np.asarray(ref))
    assert np.array_equal(np.asarray(ref), np.asarray(x))


@pytest.mark.parametrize("chunks", [1, 2])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_matmul_reduce_scatter_parity(devices, n, chunks):
    mesh = _mesh(devices, n)
    t, fl, d = 4 * n, 8 * n, 16
    rng = np.random.RandomState(n)
    y = jnp.asarray(rng.randn(t, fl), jnp.float32)
    w = jnp.asarray(rng.randn(fl, d), jnp.float32)

    def body(y_loc, w_loc):
        return matmul_reduce_scatter(y_loc, w_loc, axis_name="model",
                                     chunks=chunks)

    fn = hvdj._shard_map(
        body, mesh,
        in_specs=(P(None, "model"), P("model", None)),
        out_specs=P("model", None),
    )
    out = np.asarray(fn(y, w))
    np.testing.assert_allclose(out, np.asarray(y @ w),
                               rtol=1e-5, atol=1e-5)


def test_psum_identity(devices):
    """The algebra the fused Megatron block rests on:
    ``psum(y @ w) == all_gather(matmul_reduce_scatter(y, w))``."""
    n = 4
    mesh = _mesh(devices, n)
    t, fl, d = 16, 32, 8
    rng = np.random.RandomState(7)
    y = jnp.asarray(rng.randn(t, fl), jnp.float32)
    w = jnp.asarray(rng.randn(fl, d), jnp.float32)

    def body(y_loc, w_loc):
        z = matmul_reduce_scatter(y_loc, w_loc, axis_name="model")
        fused = lax.all_gather(z, "model", axis=0, tiled=True)
        ref = lax.psum(y_loc @ w_loc, "model")
        return jnp.max(jnp.abs(fused - ref))

    fn = hvdj._shard_map(
        body, mesh,
        in_specs=(P(None, "model"), P("model", None)),
        out_specs=P(),
    )
    assert float(fn(y, w)) <= 1e-4


# ---------------------------------------------------------------------------
# Gradient parity (the path-aware backward)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [1, 2])
def test_all_gather_matmul_gradients(devices, chunks):
    n = 4
    mesh = _mesh(devices, n)
    t, d, f = 16, 8, 12
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, f), jnp.float32)
    cot = jnp.asarray(rng.randn(t, f), jnp.float32)

    def body(x_loc, w_rep, cot_rep):
        def fused(args):
            xl, wl = args
            out = all_gather_matmul(xl, wl, axis_name="model",
                                    chunks=chunks)
            return jnp.sum(out * cot_rep)

        def ref(args):
            xl, wl = args
            full = lax.all_gather(xl, "model", axis=0, tiled=True)
            return jnp.sum((full @ wl) * cot_rep)

        return jax.grad(fused)((x_loc, w_rep)), jax.grad(ref)((x_loc, w_rep))

    fn = hvdj._shard_map(
        body, mesh,
        in_specs=(P("model", None), P(None, None), P(None, None)),
        out_specs=((P("model", None), P(None, None)),
                   (P("model", None), P(None, None))),
    )
    (dx_f, dw_f), (dx_r, dw_r) = fn(x, w, cot)
    np.testing.assert_allclose(np.asarray(dx_f), np.asarray(dx_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chunks", [1, 2])
def test_matmul_reduce_scatter_gradients(devices, chunks):
    n = 4
    mesh = _mesh(devices, n)
    t, fl, d = 16, 32, 8
    tc = t // n
    rng = np.random.RandomState(5)
    y = jnp.asarray(rng.randn(t, fl), jnp.float32)
    w = jnp.asarray(rng.randn(fl, d), jnp.float32)
    cot = jnp.asarray(rng.randn(t, d), jnp.float32)

    def body(y_loc, w_loc, cot_loc):
        def fused(args):
            yl, wl = args
            out = matmul_reduce_scatter(yl, wl, axis_name="model",
                                        chunks=chunks)
            return jnp.sum(out * cot_loc)

        def ref(args):
            yl, wl = args
            full = lax.psum(yl @ wl, "model")
            idx = lax.axis_index("model")
            own = lax.dynamic_slice_in_dim(full, idx * tc, tc, axis=0)
            return jnp.sum(own * cot_loc)

        return jax.grad(fused)((y_loc, w_loc)), jax.grad(ref)((y_loc, w_loc))

    fn = hvdj._shard_map(
        body, mesh,
        in_specs=(P(None, "model"), P("model", None), P("model", None)),
        out_specs=((P(None, "model"), P("model", None)),
                   (P(None, "model"), P("model", None))),
    )
    (dy_f, dw_f), (dy_r, dw_r) = fn(y, w, cot)
    np.testing.assert_allclose(np.asarray(dy_f), np.asarray(dy_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_r),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Composed fused step == classic step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt,param_tol", [("sgd", 5e-7), ("adamw", 1e-5)])
def test_composed_fused_matches_classic(devices, opt, param_tol):
    """The fully fused GPT step (every in-block psum replaced by
    all_gather_matmul + matmul_reduce_scatter on the token-sharded
    residual) trains identically to the classic composed step: losses
    AND final params within 5e-7 after 3 sgd steps on a 2x2 mesh. The
    two gradients agree to 1 f32 ulp; adamw divides by |g|, so that ulp
    on a gradient of 3e-5 moves a param by lr * 4e-3 — hence 1e-5 on the
    params of the adamw case (losses stay at 5e-7 there too)."""
    from horovod_tpu.models.transformer import (
        TransformerLM, make_gpt_loss_fn,
    )

    VOCAB, D, HEADS, LAYERS, T = 128, 64, 4, 2, 16
    TOL = 5e-7
    mesh = build_mesh({"data": 2, "model": 2}, devices=devices[:4])
    model = TransformerLM(vocab_size=VOCAB, d_model=D, n_heads=HEADS,
                          n_layers=LAYERS, max_len=T)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)
    )["params"]
    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randint(0, VOCAB, (4, T)), jnp.int32),
        jnp.asarray(rng.randint(0, VOCAB, (4, T)), jnp.int32),
    )
    loss_fn = make_gpt_loss_fn(HEADS, model_axis="model",
                               dtype=jnp.float32)
    tx = {"sgd": optax.sgd(1e-1), "adamw": optax.adamw(1e-3)}[opt]
    step_c = hvdj.make_train_step(loss_fn, tx, mesh, rules="gpt",
                                  donate=False)
    step_f = hvdj.make_train_step(loss_fn, tx, mesh, rules="gpt",
                                  tp_overlap=True, donate=False)

    def train(step):
        p, s, losses = params, tx.init(params), []
        for _ in range(3):
            p, s, loss = step(p, s, batch)
            losses.append(float(loss))
        return p, losses

    pc, losses_c = train(step_c)
    pf, losses_f = train(step_f)
    for a, b in zip(losses_c, losses_f):
        assert abs(a - b) <= TOL * max(1.0, abs(a)), (losses_c, losses_f)
    perr = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(pc), jax.tree.leaves(pf))
    )
    assert perr <= param_tol, f"fused/classic param divergence {perr}"


def test_tp_overlap_requires_rules(devices):
    import horovod_tpu.jax as hj

    mesh = build_mesh({"data": 2}, devices=devices[:2])
    with pytest.raises(ValueError, match="tp_overlap"):
        hj.make_train_step(lambda p, b: jnp.float32(0), optax.sgd(0.1),
                           mesh, tp_overlap=True)


# ---------------------------------------------------------------------------
# Wire attribution: exact and chunk-count-invariant
# ---------------------------------------------------------------------------

def _model_axis_wire(devices, n, chunks, primitive):
    import horovod_tpu.metrics as metrics

    mesh = _mesh(devices, n)
    rng = np.random.RandomState(1)
    metrics.install(True)
    try:
        if primitive == "all_gather_matmul":
            t, d, f = 4 * n, 8, 8
            x = jnp.asarray(rng.randn(t, d), jnp.float32)
            w = jnp.asarray(rng.randn(d, f), jnp.float32)
            fn = hvdj._shard_map(
                lambda xl, wl: all_gather_matmul(
                    xl, wl, axis_name="model", chunks=chunks
                ),
                mesh,
                in_specs=(P("model", None), P(None, None)),
                out_specs=P(None, None),
            )
            fn(x, w)
        else:
            t, fl, d = 4 * n, 8 * n, 8
            y = jnp.asarray(rng.randn(t, fl), jnp.float32)
            w = jnp.asarray(rng.randn(fl, d), jnp.float32)
            fn = hvdj._shard_map(
                lambda yl, wl: matmul_reduce_scatter(
                    yl, wl, axis_name="model", chunks=chunks
                ),
                mesh,
                in_specs=(P(None, "model"), P("model", None)),
                out_specs=P("model", None),
            )
            fn(y, w)
        return {
            k: v for k, v in metrics.flat().items()
            if "hvd_axis_wire_bytes_total" in k and 'axis="model"' in k
        }
    finally:
        metrics.install(False)


def test_all_gather_matmul_wire_bytes_exact(devices):
    n, t, d = 4, 16, 8
    tc = t // n
    by_chunks = {
        c: _model_axis_wire(devices, n, c, "all_gather_matmul")
        for c in (1, 2)
    }
    for c, axis in by_chunks.items():
        (key,) = axis.keys()
        assert 'collective="all_gather_matmul"' in key, axis
        # _record charges the full gathered payload (shard * n); the
        # ring moves (n-1)/n of it: (n-1) * shard bytes.
        assert axis[key] == (n - 1) * tc * d * 4, axis
    # Sub-chunking re-pipelines; it never changes bytes on wire.
    assert by_chunks[1] == by_chunks[2]


def test_matmul_reduce_scatter_wire_bytes_exact(devices):
    n, t, d = 4, 16, 8
    by_chunks = {
        c: _model_axis_wire(devices, n, c, "matmul_reduce_scatter")
        for c in (1, 2)
    }
    for c, axis in by_chunks.items():
        (key,) = axis.keys()
        assert 'collective="matmul_reduce_scatter"' in key, axis
        # Output-token payload t*d, one ring pass: (n-1)/n of it.
        assert axis[key] == (n - 1) * (t * d * 4) // n, axis
    assert by_chunks[1] == by_chunks[2]


def test_backward_records_dual_primitive(devices):
    """The backward's wire shows up under the DUAL primitive's label —
    an AG-matmul VJP pays one matmul_reduce_scatter plus one more
    all_gather_matmul pass (the weight-grad ring)."""
    import horovod_tpu.metrics as metrics

    n, t, d, f = 4, 16, 8, 8
    tc = t // n
    mesh = _mesh(devices, n)
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, f), jnp.float32)
    metrics.install(True)
    try:
        def body(x_loc, w_rep):
            def loss(args):
                out = all_gather_matmul(args[0], args[1],
                                        axis_name="model")
                return jnp.sum(out * out)

            return jax.grad(loss)((x_loc, w_rep))

        fn = hvdj._shard_map(
            body, mesh,
            in_specs=(P("model", None), P(None, None)),
            out_specs=(P("model", None), P(None, None)),
        )
        fn(x, w)
        axis = {
            k: v for k, v in metrics.flat().items()
            if "hvd_axis_wire_bytes_total" in k and 'axis="model"' in k
        }
    finally:
        metrics.install(False)
    ag = sum(v for k, v in axis.items()
             if 'collective="all_gather_matmul"' in k)
    mrs = sum(v for k, v in axis.items()
              if 'collective="matmul_reduce_scatter"' in k)
    # fwd AG pass + bwd weight-grad AG pass: 2 x (n-1) * shard bytes.
    assert ag == 2 * (n - 1) * tc * d * 4, axis
    # bwd dx = reduce_scatter(ct @ w^T): (n-1)/n of the t*f cotangent.
    assert mrs == (n - 1) * (t * f * 4) // n, axis
    assert not any('collective="psum"' in k for k in axis), axis


# ---------------------------------------------------------------------------
# Symbolic plan verification (analysis/plan_verify Pass 3)
# ---------------------------------------------------------------------------

def _tp_model(n):
    from horovod_tpu.topo.model import synthetic_model
    from horovod_tpu.tune.objective import tp_inner_model

    return tp_inner_model(synthetic_model(16), n)


def test_plan_verifier_clean_sweep():
    from horovod_tpu.analysis.plan_verify import verify_plan
    from horovod_tpu.common.quant import WIRE_BF16, WIRE_F32
    from horovod_tpu.topo.compositor import (
        COLLECTIVE_MATMUL_FLAVORS, collective_matmul_plan,
    )

    for flavor, n, chunks, wire in itertools.product(
        COLLECTIVE_MATMUL_FLAVORS, (2, 4, 8), (1, 2, 4),
        (WIRE_F32, WIRE_BF16),
    ):
        model = _tp_model(n)
        plan = collective_matmul_plan(model, flavor, 1 << 16,
                                      chunks=chunks, wire_dtype=wire)
        findings = verify_plan(plan, model)
        assert findings == [], (
            flavor, n, chunks, wire, [f.message for f in findings]
        )


def test_plan_verifier_flags_doubled_bytes():
    from horovod_tpu.analysis.findings import RULE_PLAN_BYTES
    from horovod_tpu.analysis.plan_verify import verify_plan
    from horovod_tpu.topo.compositor import collective_matmul_plan

    model = _tp_model(4)
    plan = collective_matmul_plan(model, "all_gather_matmul", 1 << 16,
                                  chunks=2)
    stages = list(plan.stages)
    stages[0] = dataclasses.replace(
        stages[0], bytes_on_wire=stages[0].bytes_on_wire * 2
    )
    bad = dataclasses.replace(plan, stages=tuple(stages))
    findings = verify_plan(bad, model)
    assert any(f.rule == RULE_PLAN_BYTES for f in findings), findings


def test_plan_verifier_flags_dropped_chunk():
    from horovod_tpu.analysis.plan_verify import verify_plan
    from horovod_tpu.topo.compositor import collective_matmul_plan

    model = _tp_model(4)
    nbytes = 1 << 16
    plan = collective_matmul_plan(model, "all_gather_matmul", nbytes,
                                  chunks=2)
    # Drop one of the fwd ring's two chunks: halve the round tag AND
    # keep bytes self-consistent with the smaller tag — only the
    # coverage check can catch the hole (offset 2 never delivered).
    stages = list(plan.stages)
    assert "fwd-r4-ring" in stages[0].primitive, stages[0]
    stages[0] = dataclasses.replace(
        stages[0],
        primitive=stages[0].primitive.replace("-r4-", "-r2-"),
        rounds=2,
        bytes_on_wire=nbytes * 1 // 4,
    )
    bad = dataclasses.replace(plan, stages=tuple(stages))
    findings = verify_plan(bad, model)
    assert findings, "dropped chunk went undetected"
    assert any("unreached" in f.message for f in findings), [
        f.message for f in findings
    ]


def test_plan_verifier_flags_non_bijective_round():
    from horovod_tpu.analysis.findings import RULE_PLAN_BIJECTION
    from horovod_tpu.analysis.plan_verify import perm_rounds, verify_plan
    from horovod_tpu.topo.compositor import collective_matmul_plan

    model = _tp_model(4)
    plan = collective_matmul_plan(model, "matmul_reduce_scatter",
                                  1 << 16, chunks=2)

    def bad_rounds(primitive, g):
        rounds = perm_rounds(primitive, g)
        if not rounds:
            return rounds
        r0 = list(rounds[0])
        if len(r0) >= 2:
            # Two sources now hit one destination: not a bijection.
            r0[1] = (r0[1][0], r0[0][1])
        return [r0] + [list(r) for r in rounds[1:]]

    assert verify_plan(plan, model) == []
    findings = verify_plan(plan, model, rounds_fn=bad_rounds)
    assert any(f.rule == RULE_PLAN_BIJECTION for f in findings), findings


def test_plan_verifier_flags_unknown_algorithm():
    from horovod_tpu.analysis.plan_verify import verify_plan
    from horovod_tpu.topo.compositor import collective_matmul_plan

    model = _tp_model(4)
    plan = collective_matmul_plan(model, "all_gather_matmul", 1 << 16)
    bad = dataclasses.replace(plan, algorithm="all_gather_matmul")
    findings = verify_plan(bad, model)
    assert any("unknown collective_matmul algorithm" in f.message
               for f in findings), findings


def test_plan_rejects_int8_wire():
    from horovod_tpu.common.quant import WIRE_INT8
    from horovod_tpu.topo.compositor import collective_matmul_plan

    with pytest.raises(ValueError, match="bf16"):
        collective_matmul_plan(_tp_model(4), "all_gather_matmul",
                               1 << 16, wire_dtype=WIRE_INT8)


# ---------------------------------------------------------------------------
# Knob registry
# ---------------------------------------------------------------------------

def _tp_knobs_in_sources():
    found = set()
    for root, _dirs, files in os.walk(os.path.join(REPO, "horovod_tpu")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                found.update(re.findall(r"HOROVOD_TP_[A-Z_]+", f.read()))
    return found


def test_every_tp_overlap_knob_is_declared_in_env():
    knobs = _tp_knobs_in_sources()
    assert hvd_env.HOROVOD_TP_OVERLAP in knobs
    assert hvd_env.HOROVOD_TP_OVERLAP_CHUNKS in knobs
    for knob in sorted(knobs):
        assert getattr(hvd_env, knob, None) == knob, (
            f"{knob} is referenced in sources but not declared in "
            f"common/env.py — unknown TP-overlap knobs are a bug"
        )


def test_config_from_env_parses_tp_overlap_knobs(monkeypatch):
    monkeypatch.setenv(hvd_env.HOROVOD_TP_OVERLAP, "1")
    monkeypatch.setenv(hvd_env.HOROVOD_TP_OVERLAP_CHUNKS, "4")
    cfg = hvd_env.Config.from_env()
    assert cfg.tp_overlap is True
    assert cfg.tp_overlap_chunks == 4
    monkeypatch.setenv(hvd_env.HOROVOD_TP_OVERLAP, "0")
    assert hvd_env.Config.from_env().tp_overlap is False
