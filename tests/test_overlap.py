"""Streamed (overlap) gradient reduction — docs/overlap.md.

Three claims under test:

1. NUMERICS — ``overlap=True`` is bit-identical to ``overlap=False`` and to
   the unfused per-leaf psum on an f32 CPU mesh (elementwise reductions
   commute with any bucket/group split; scaling divides by a power of two),
   at 2 and 4 ranks, across make_train_step / DistributedOptimizer /
   GradientAccumulator, with quantized/adasum composition rejected.
2. STRUCTURE — the lowered HLO of a 3-layer MLP step with overlap=True
   contains >= 3 independent gradient all-reduces (vs the single
   barrier-like reduction today), each depending only on its layer suffix.
3. KNOBS — HOROVOD_FUSION_THRESHOLD / HOROVOD_FUSION_FIRST_BUCKET_BYTES
   defaults, the bucket/group planners, the perf-flag preset resolver, and
   the overlap-no-streaming lint.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvdj
from horovod_tpu.common import env as env_mod
from horovod_tpu.common.types import Adasum, ReduceOp
from horovod_tpu.jax import _shard_map
from horovod_tpu.ops import fusion as F
from horovod_tpu.parallel.mesh import build_mesh

D = 12


def _params(n_layers=3, seed=1):
    rng = np.random.RandomState(seed)
    return {
        f"layer{i}": {
            "w": jnp.asarray(rng.randn(D, D).astype(np.float32) * 0.3),
            "b": jnp.zeros((D,), jnp.float32),
        }
        for i in range(n_layers)
    }


def _loss_fn(params, batch):
    X, y = batch
    h = X
    for k in sorted(params):
        h = jnp.tanh(h @ params[k]["w"] + params[k]["b"])
    return jnp.mean((h - y) ** 2)


def _batch(n_rows, seed=0):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randn(n_rows, D).astype(np.float32)),
        jnp.asarray(rng.randn(n_rows, D).astype(np.float32)),
    )


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --- 1. numeric parity -------------------------------------------------------

@pytest.mark.parametrize("n_ranks", [2, 4])
def test_train_step_overlap_bitwise_parity(n_ranks):
    """overlap=True == overlap=False == unfused per-leaf psum, bitwise,
    on a 2- and 4-rank f32 CPU mesh."""
    mesh = build_mesh(
        {"data": n_ranks}, devices=jax.devices()[:n_ranks]
    )
    params = _params()
    tx = optax.sgd(0.05)
    batch = _batch(4 * n_ranks)

    step_ov = hvdj.make_train_step(
        _loss_fn, tx, mesh, donate=False, overlap=True,
        fusion_threshold_bytes=1 << 16, first_bucket_bytes=1,
    )
    step_df = hvdj.make_train_step(_loss_fn, tx, mesh, donate=False)

    def unfused_step(p, s, b):
        loss, grads = jax.value_and_grad(_loss_fn)(p, b)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "data"), grads)
        updates, s = tx.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return p, s, jax.lax.pmean(loss, "data")

    step_uf = jax.jit(_shard_map(
        unfused_step, mesh, in_specs=(P(), P(), P("data")), out_specs=P()
    ))

    states = [(params, tx.init(params)) for _ in range(3)]
    for _ in range(5):
        outs = []
        for step, (p, s) in zip((step_ov, step_df, step_uf), states):
            outs.append(step(p, s, batch))
        states = [(o[0], o[1]) for o in outs]
        _tree_equal(states[0][0], states[1][0])
        _tree_equal(states[0][0], states[2][0])
        assert float(outs[0][2]) == float(outs[1][2]) == float(outs[2][2])


def test_distributed_optimizer_overlap_parity():
    """DistributedOptimizer(overlap=True) + registered streaming matches
    the post-hoc wrapper bitwise."""
    mesh = build_mesh()
    params = _params()
    batch = _batch(16)

    tx_ov = hvdj.DistributedOptimizer(optax.sgd(0.05), overlap=True)
    tx_df = hvdj.DistributedOptimizer(optax.sgd(0.05))

    def step_streamed(p, s, b):
        def streamed_loss(p_, b_):
            return _loss_fn(
                hvdj.stream_param_groups(p_, first_bucket_bytes=1), b_
            )

        loss, grads = jax.value_and_grad(streamed_loss)(p, b)
        u, s = tx_ov.update(grads, s, p)
        return optax.apply_updates(p, u), s, jax.lax.pmean(loss, "data")

    def step_plain(p, s, b):
        loss, grads = jax.value_and_grad(_loss_fn)(p, b)
        u, s = tx_df.update(grads, s, p)
        return optax.apply_updates(p, u), s, jax.lax.pmean(loss, "data")

    f1 = jax.jit(_shard_map(
        step_streamed, mesh, in_specs=(P(), P(), P("data")), out_specs=P()
    ))
    f2 = jax.jit(_shard_map(
        step_plain, mesh, in_specs=(P(), P(), P("data")), out_specs=P()
    ))
    p1, s1 = params, tx_ov.init(params)
    p2, s2 = params, tx_df.init(params)
    for _ in range(3):
        p1, s1, l1 = f1(p1, s1, batch)
        p2, s2, l2 = f2(p2, s2, batch)
    _tree_equal(p1, p2)
    assert float(l1) == float(l2)


def test_distributed_optimizer_overlap_fallback_warns(caplog):
    """overlap=True with NO registered streaming must warn loudly and fall
    back to the post-hoc reduction (same numbers as overlap=False)."""
    import logging

    mesh = build_mesh()
    params = _params()
    batch = _batch(16)
    tx_ov = hvdj.DistributedOptimizer(optax.sgd(0.05), overlap=True)
    tx_df = hvdj.DistributedOptimizer(optax.sgd(0.05))

    def mk(tx):
        def step(p, s, b):
            loss, grads = jax.value_and_grad(_loss_fn)(p, b)
            u, s = tx.update(grads, s, p)
            return optax.apply_updates(p, u), s, jax.lax.pmean(loss, "data")

        return jax.jit(_shard_map(
            step, mesh, in_specs=(P(), P(), P("data")), out_specs=P()
        ))

    F.take_stream_registrations()  # drop any leftover registrations
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        p1, s1, _ = mk(tx_ov)(params, tx_ov.init(params), batch)
    assert any("overlap-no-streaming" in r.message for r in caplog.records)
    p2, s2, _ = mk(tx_df)(params, tx_df.init(params), batch)
    _tree_equal(p1, p2)


def test_gradient_accumulator_with_overlap():
    """Microbatch accumulation: streamed per-microbatch reduction sums to
    the same update as accumulate-then-reduce (linear ops; float
    reassociation across microbatches -> allclose, not bitwise)."""
    mesh = build_mesh()
    params = _params()
    acc = hvdj.GradientAccumulator(2)
    batches = [_batch(16, seed=i) for i in range(2)]

    def grads_streamed(p, b):
        def streamed_loss(p_, b_):
            return _loss_fn(
                hvdj.stream_param_groups(p_, first_bucket_bytes=1), b_
            )

        return jax.grad(streamed_loss)(p, b)

    def grads_plain(p, b):
        return jax.grad(_loss_fn)(p, b)

    g_s = jax.jit(_shard_map(
        grads_streamed, mesh, in_specs=(P(), P("data")), out_specs=P()
    ))
    g_p = jax.jit(_shard_map(
        grads_plain, mesh, in_specs=(P(), P("data")), out_specs=P()
    ))

    a_s = acc.init(params)
    local = acc.init(params)
    for b in batches:
        a_s = acc.add(a_s, g_s(params, b))       # reduced each microbatch
        local = acc.add(local, g_p(params, b))   # reduce once at the end
    red = jax.jit(_shard_map(
        lambda g: jax.tree.map(lambda t: jax.lax.pmean(t, "data"), g),
        mesh, in_specs=(P(),), out_specs=P(),
    ))(local)
    for x, y in zip(jax.tree.leaves(a_s), jax.tree.leaves(red)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7
        )


def test_stream_scan_body_bitwise_parity():
    """Scanned layer stack: per-iteration streamed psums equal the psum of
    the accumulated stacked gradient, bitwise."""
    mesh = build_mesh()
    rng = np.random.RandomState(2)
    ws = jnp.asarray(rng.randn(4, D, D).astype(np.float32) * 0.3)
    x0 = jnp.asarray(rng.randn(8, D).astype(np.float32))

    def body(h, w):
        return jnp.tanh(h @ w), None

    def loss_streamed(ws, x):
        h, _ = jax.lax.scan(hvdj.stream_scan_body(body), x, ws)
        return jnp.mean(h ** 2)

    def loss_plain(ws, x):
        h, _ = jax.lax.scan(body, x, ws)
        return jnp.mean(h ** 2)

    gs = jax.jit(_shard_map(
        lambda w, x: jax.grad(loss_streamed)(w, x), mesh,
        in_specs=(P(), P("data")), out_specs=P(),
    ))(ws, x0)
    gp = jax.jit(_shard_map(
        lambda w, x: jax.tree.map(
            lambda t: jax.lax.pmean(t, "data"),
            jax.grad(loss_plain)(w, x),
        ),
        mesh, in_specs=(P(), P("data")), out_specs=P(),
    ))(ws, x0)
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(gp))


def test_overlap_rejects_adasum_and_bad_quantized_compositions():
    """overlap+quantized is now first-class (PR 9); what stays rejected:
    ADASUM streaming, quantized MIN/MAX, quantized+cast-compression, and
    error feedback on the hierarchical (DCN-only) wire."""
    from horovod_tpu.common.compression import Compression

    mesh = build_mesh()
    with pytest.raises(ValueError, match="SUM/AVERAGE|quantized"):
        hvdj.make_train_step(
            _loss_fn, optax.sgd(0.1), mesh, overlap=True, quantized=True,
            op=ReduceOp.MIN,
        )
    with pytest.raises(ValueError, match="already compresses"):
        hvdj.make_train_step(
            _loss_fn, optax.sgd(0.1), mesh, overlap=True, quantized=True,
            compression=Compression.fp16,
        )
    with pytest.raises(ValueError, match="error feedback|error_feedback"):
        hvdj.make_train_step(
            _loss_fn, optax.sgd(0.1), mesh, quantized=True,
            hierarchical=True, error_feedback=True,
        )
    with pytest.raises(ValueError, match="elementwise"):
        hvdj.make_train_step(
            _loss_fn, optax.sgd(0.1), mesh, overlap=True, op=Adasum
        )
    with pytest.raises(ValueError, match="elementwise"):
        F.reduce_in_backward(_params(), op=ReduceOp.ADASUM)
    with pytest.raises(ValueError, match="quantized streaming"):
        F.reduce_in_backward(_params(), op=ReduceOp.MIN, quantized=True)
    from horovod_tpu.ops.quantized import ef_like

    with pytest.raises(ValueError, match="flat int8 ring"):
        F.reduce_in_backward(
            _params(), quantized=True, hierarchical=True,
            ef=ef_like(_params()),
        )


def test_overlap_hierarchical_matches_flat():
    from horovod_tpu.parallel.mesh import build_hierarchical_mesh

    hmesh = build_hierarchical_mesh(local_size=4)
    mesh = build_mesh()
    params = _params()
    tx = optax.sgd(0.05)
    batch = _batch(16)
    step_h = hvdj.make_train_step(
        _loss_fn, tx, hmesh, donate=False, overlap=True, hierarchical=True,
        first_bucket_bytes=1,
    )
    step_f = hvdj.make_train_step(_loss_fn, tx, mesh, donate=False)
    ph, sh = params, tx.init(params)
    pf, sf = params, tx.init(params)
    for _ in range(3):
        ph, sh, lh = step_h(ph, sh, batch)
        pf, sf, lf = step_f(pf, sf, batch)
    for x, y in zip(jax.tree.leaves(ph), jax.tree.leaves(pf)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5)


# --- 2. structure ------------------------------------------------------------

def _count_grad_allreduces(lowered) -> int:
    hlo = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    return sum(
        1 for line in hlo.splitlines()
        if re.search(r"\ball-reduce\(", line)
        and "=" in line
        and not re.match(r"^\s*[%\w.\-]+\s*=\s*\(?\s*\w+\[\]", line)
    )


def test_overlap_lowered_hlo_has_independent_allreduces():
    """The acceptance structure: a 3-layer MLP with overlap=True lowers to
    >= 3 gradient all-reduces; the default path keeps the single fused
    barrier reduction."""
    mesh = build_mesh()
    params = _params()
    tx = optax.sgd(0.05)
    batch = _batch(16)
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (params, tx.init(params), batch),
    )

    # Tiny caps force one streamed group per layer on the toy model (a
    # real model hits this shape with the default 64 MB / 1 MB knobs).
    step_ov = hvdj.make_train_step(
        _loss_fn, tx, mesh, donate=False, overlap=True,
        fusion_threshold_bytes=1, first_bucket_bytes=1,
    )
    step_df = hvdj.make_train_step(_loss_fn, tx, mesh, donate=False)
    n_ov = _count_grad_allreduces(step_ov.lower(*avals))
    n_df = _count_grad_allreduces(step_df.lower(*avals))
    assert n_ov >= 3, n_ov
    assert n_df == 1, n_df


# --- 3. planners, knobs, lint ------------------------------------------------

def test_plan_buckets_oversized_leaf_keeps_packing():
    """An oversized leaf closes the dtype's active bucket; later small
    same-dtype leaves fuse into a FRESH bucket (not singletons, and not
    the pre-oversized bucket — emission order stays monotone)."""
    small = np.zeros((100,), np.float32)     # 400 B
    big = np.zeros((1000,), np.float32)      # 4000 B >= threshold
    plan = F.plan_buckets(
        [small, small, big, small, small], threshold_bytes=1000
    )
    assert plan == [[0, 1], [2], [3, 4]]


def test_plan_buckets_mixed_dtype_plan_locked():
    f32 = np.zeros((100,), np.float32)
    i32 = np.zeros((50,), np.int32)
    big = np.zeros((1000,), np.float32)
    plan = F.plan_buckets(
        [f32, i32, f32, big, i32, f32], threshold_bytes=1000
    )
    # f32: 0,2 fuse; big closes the f32 bucket; 5 restarts fresh.
    # i32: 1,4 fuse (their bucket was never interrupted).
    assert plan == [[0, 2], [1, 4], [3], [5]]


def test_plan_layer_groups_reverse_order_small_first_bucket():
    # layers of 100 B each; first bucket 150 B, threshold 250 B.
    groups = F.plan_layer_groups([100] * 6, 250, 150)
    # reduction order: last layers first, small first group.
    assert groups == [[4, 5], [1, 2, 3], [0]]


def test_fusion_threshold_env_default(monkeypatch):
    monkeypatch.setenv(env_mod.HOROVOD_FUSION_THRESHOLD, "1234")
    assert F.default_threshold_bytes(None) == 1234
    assert F.default_threshold_bytes(99) == 99
    monkeypatch.setenv(env_mod.HOROVOD_FUSION_FIRST_BUCKET_BYTES, "77")
    assert F.default_first_bucket_bytes(None) == 77
    assert F.default_first_bucket_bytes(5) == 5
    cfg = env_mod.Config.from_env()
    assert cfg.fusion_threshold_bytes == 1234
    assert cfg.fusion_first_bucket_bytes == 77


def test_fusion_threshold_env_reaches_bucket_plan(monkeypatch):
    """HOROVOD_FUSION_THRESHOLD must be the live default inside
    fused_allreduce: a tiny threshold forces per-leaf buckets in the
    lowered step HLO."""
    monkeypatch.setenv(env_mod.HOROVOD_FUSION_THRESHOLD, "1")
    mesh = build_mesh()
    params = _params()
    tx = optax.sgd(0.05)
    batch = _batch(16)
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (params, tx.init(params), batch),
    )
    step = hvdj.make_train_step(_loss_fn, tx, mesh, donate=False)
    # 6 leaves -> 6 per-leaf all-reduces instead of the single fused one.
    assert _count_grad_allreduces(step.lower(*avals)) == 6


def test_perf_preset_resolution(monkeypatch):
    monkeypatch.delenv(env_mod.HOROVOD_XLA_PERF_PRESET, raising=False)
    # Nothing is applied unless asked for, whatever JAX_PLATFORMS says.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    name, flags = env_mod.resolve_perf_preset(None)
    assert name == "off" and flags == {}
    name, flags = env_mod.resolve_perf_preset("overlap")
    assert name == "overlap"
    assert flags["xla_tpu_enable_latency_hiding_scheduler"] == "true"
    monkeypatch.setenv(env_mod.HOROVOD_XLA_PERF_PRESET, "overlap")
    assert env_mod.resolve_perf_preset(None)[0] == "overlap"
    with pytest.raises(ValueError, match="unknown"):
        env_mod.resolve_perf_preset("warpspeed")


def test_perf_preset_application_idempotent(monkeypatch):
    monkeypatch.setenv(
        "LIBTPU_INIT_ARGS", "--xla_tpu_enable_latency_hiding_scheduler=false"
    )
    xla_flags = os.environ.get("XLA_FLAGS")
    record = env_mod.apply_xla_perf_preset("overlap")
    # jaxlib refuses these names in XLA_FLAGS; they are libtpu's.
    assert os.environ.get("XLA_FLAGS") == xla_flags
    flags = os.environ["LIBTPU_INIT_ARGS"]
    # The user's explicit setting wins; the missing flags are appended.
    assert flags.count("xla_tpu_enable_latency_hiding_scheduler") == 1
    assert "--xla_enable_async_all_reduce=true" in flags
    assert record["preset"] == "overlap"
    assert "xla_tpu_enable_latency_hiding_scheduler" not in record["applied"]
    assert env_mod.applied_perf_preset() is record
    # Re-application adds nothing.
    again = env_mod.apply_xla_perf_preset("overlap")
    assert os.environ["LIBTPU_INIT_ARGS"] == flags
    assert again["applied"] == []


def test_overlap_streaming_lint():
    from horovod_tpu.analysis.findings import RULE_OVERLAP_STREAMING
    from horovod_tpu.analysis.preflight import check_overlap_streaming

    none = check_overlap_streaming({"calls": 0, "leaves": 0}, 6)
    assert [f.rule for f in none] == [RULE_OVERLAP_STREAMING]
    assert "no parameter subtree" in none[0].message
    partial = check_overlap_streaming({"calls": 1, "leaves": 2}, 6)
    assert [f.rule for f in partial] == [RULE_OVERLAP_STREAMING]
    assert "PARTIAL" in partial[0].message
    assert check_overlap_streaming({"calls": 3, "leaves": 6}, 6) == []


def test_overlap_metrics_gauges():
    from horovod_tpu import metrics

    metrics.install(True)
    try:
        mesh = build_mesh()
        params = _params()
        tx = optax.sgd(0.05)
        batch = _batch(16)
        step = hvdj.make_train_step(
            _loss_fn, tx, mesh, donate=False, overlap=True,
            fusion_threshold_bytes=1, first_bucket_bytes=1,
        )
        step(params, tx.init(params), batch)
        snap = metrics.snapshot()
        assert snap["hvd_overlap_groups"]["series"][0]["value"] >= 3
        assert "hvd_fusion_buckets" in snap
        paths = {
            tuple(s["labels"].items())
            for s in snap["hvd_fusion_buckets"]["series"]
        }
        assert any("stream" in str(p) for p in paths)
        assert "hvd_fusion_bucket_bytes" in snap
    finally:
        metrics.reset()


def test_overlap_schedule_parser():
    """The HLO-schedule parser of tools/tpu_profile_overlap.py (phase B):
    async pairs are matched by operand name including TUPLE-typed
    (variadic) forms — a miss there would turn real latency hiding into a
    false 'no overlap' verdict — and compute between start/done is counted
    across tuple-shaped fusions."""
    import importlib.util
    import os

    repo = os.path.join(os.path.dirname(__file__), os.pardir)
    spec = importlib.util.spec_from_file_location(
        "tpo", os.path.join(repo, "tools", "tpu_profile_overlap.py")
    )
    tpo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tpo)

    hlo = "\n".join([
        "ENTRY %main {",
        "  %p0 = f32[8]{0} parameter(0)",
        "  %ars = (f32[64]{0}, f32[32]{0}) all-reduce-start(%g1, %g2), "
        "replica_groups={{0,1}}",
        "  %f.1 = (f32[64]{0}, f32[8]{0}) fusion(%p0), kind=kLoop",
        "  %conv = f32[1,8,8,64]{3,2,1,0} convolution(%x, %k), window={}",
        "  %ard = (f32[64]{0}, f32[32]{0}) all-reduce-done(%ars)",
        "  %sync = f32[64]{0} all-reduce(%f.1), replica_groups={{0,1}}",
        "  %gte = f32[64]{0} get-tuple-element(%ard), index=0",
        "}",
    ])
    stats = tpo._schedule_overlap_stats(hlo)
    assert stats["async_all_reduce_pairs"] == 1, stats
    assert stats["compute_ops_overlapped_per_pair"] == [2], stats
    assert stats["pairs_with_overlap"] == 1, stats
    assert stats["sync_all_reduce_count"] == 1, stats
