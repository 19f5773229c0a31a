"""``ops/stream_mix.py``: the four kernels (interpreted on the CPU) against
the XLA form they replace, in value and in every gradient; the split products
against ``HIGHEST`` products of the same float32 operands; the plan's
refusals and their record; the scope every op is written under.

Tolerances. The maps are float32 in both forms and differ by the order of
float32 additions: 1e-5 of their size. ``h`` and ``X'`` are bfloat16 sums
rounded once: a rounding that falls the other way is one bfloat16 step
(2^-8 of the value). A gradient that passes through a bfloat16 cotangent
(``dy``, ``dh``, the streams') differs by such steps in a few elements:
1e-2 of the leaf's norm, where a dropped term or a wrong map reads 0.1 to
1; ``phi``, ``alpha`` and ``b`` see only float32 sums of them: 1e-4."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import trace as hvd_trace
from horovod_tpu.ops import stream_mix as sm

SPEC = sm.Spec(1e-6, 1e-6, (-30.0, 30.0), 20)
HIGHEST = jax.lax.Precision.HIGHEST
SHAPES = [(1, 256, 128), (2, 128, 256)]        # B, T, C at n = 4


def _inputs(B, T, C, seed=0, n=4, dtype=jnp.bfloat16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    maps = 2 * n + n * n
    return (jax.random.normal(k[0], (n, B, T, C)).astype(dtype),
            jax.random.normal(k[1], (B, T, C)).astype(dtype),
            0.05 * jax.random.normal(k[2], (n * C, maps)),
            1.0 + 0.2 * jax.random.normal(k[3], (3,)),
            jax.random.normal(k[4], (maps,)))


def _by_kernels(streams, y, phi, alpha, b):
    h, post, res, carried = sm.pre(streams, phi, alpha, b, SPEC)
    return h, post, res, sm.post(carried, y, post, res)


def _by_xla(streams, y, phi, alpha, b):
    h, post, res = sm._xla_pre(streams, phi, alpha, b, SPEC)
    return h, post, res, sm._xla_post(streams, y, post, res)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("B,T,C", SHAPES)
def test_values_equal_the_xla_form(B, T, C):
    args = _inputs(B, T, C)
    hvd_trace.reset_build_ledger()
    got, want = _by_kernels(*args), _by_xla(*args)
    assert hvd_trace.build_ledger()["fallbacks"] == []
    assert hvd_trace.plan_args()["hc_mix_tile"] == sm.plan(
        4, T, C, jnp.bfloat16)
    for name, a, r in zip(("h", "post", "res", "out"), got, want):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        step = 2.0 ** -7 if a.dtype == jnp.bfloat16 else 1e-5
        assert np.all(np.abs(_f32(a) - _f32(r))
                      <= step * np.maximum(np.abs(_f32(r)), 1.0)), name


@pytest.mark.parametrize("B,T,C", SHAPES)
@pytest.mark.parametrize("leaf,tol", [
    ("streams", 1e-2), ("y", 1e-2), ("phi", 1e-4), ("alpha", 1e-4),
    ("b", 1e-4)])
def test_gradients_equal_the_xla_form(B, T, C, leaf, tol):
    """Every output weighted by a fixed draw, so that each map's cotangent
    is its own; the sublayer between the halves is ``y + h``."""
    args = _inputs(B, T, C, seed=1)
    at = ("streams", "y", "phi", "alpha", "b").index(leaf)
    shapes = jax.eval_shape(_by_xla, *args)
    keys = jax.random.split(jax.random.PRNGKey(7), len(shapes))
    weights = [jax.random.normal(k, s.shape) for k, s in zip(keys, shapes)]

    def loss(form):
        def f(streams, y, phi, alpha, b):
            h, post, res, carried = (
                sm.pre(streams, phi, alpha, b, SPEC) if form == "kernels"
                else sm._xla_pre(streams, phi, alpha, b, SPEC) + (streams,))
            mixed = (sm.post if form == "kernels" else sm._xla_post)(
                carried, y + h, post, res)
            return sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(
                (h, post, res, mixed), weights))
        return f

    got = jax.grad(loss("kernels"), argnums=at)(*args)
    want = jax.grad(loss("xla"), argnums=at)(*args)
    assert got.dtype == want.dtype == args[at].dtype
    if leaf == "phi":
        assert got.dtype == jnp.float32
    gap = np.linalg.norm(_f32(got) - _f32(want))
    assert gap <= tol * np.linalg.norm(_f32(want)), (leaf, gap)


def test_the_sinkhorn_planes_are_not_kept():
    """The backward reads the streams, the maps ``post`` takes and ``2n + n
    n + 1`` float32 planes a mix: no round of the twenty leaves a plane."""
    B, T, C = 1, 128, 256
    streams, y, phi, alpha, b = _inputs(B, T, C)
    _, vjp = jax.vjp(lambda *a: sm.pre(*a, SPEC), streams, phi, alpha, b)
    kept = sorted(x.shape for x in jax.tree.leaves(vjp) if hasattr(x, "shape"))
    assert (B, 25, T) in kept
    planes = sum(int(np.prod(s)) for s in kept if s[-1] == T)
    assert planes == 25 * B * T


def _split_dot(x, w):
    """``x`` (bfloat16) times ``w`` (float32) as the kernels multiply them:
    one bfloat16 product against the three terms side by side."""
    terms = jnp.concatenate(sm.split3(w), axis=1)
    parts = jnp.dot(x, terms, preferred_element_type=jnp.float32)
    m = w.shape[1]
    return (parts[:, 2 * m:] + parts[:, m:2 * m]) + parts[:, :m]


def _six_pair_dot(g, w):
    """``g @ w^T`` with both float32, as one bfloat16 product whose
    contraction holds the six pairs a ``HIGHEST`` product forms."""
    g_hi, g_mid, g_lo = sm.split3(g)
    w_hi, w_mid, w_lo = sm.split3(w)
    left = jnp.concatenate([g_hi, g_hi, g_mid, g_hi, g_lo, g_mid], axis=1)
    right = jnp.concatenate([w_hi, w_mid, w_hi, w_lo, w_hi, w_mid], axis=1)
    return jnp.dot(left, right.T, preferred_element_type=jnp.float32)


@pytest.mark.parametrize("product", ["forward", "d_phi", "d_x"])
def test_split_product_is_the_float32_product(product):
    """Equal to ``precision=HIGHEST`` of the same float32 operands to the
    rounding of a float32 sum, and NOT equal to the default-precision
    product: ``mid`` and ``lo`` are in it."""
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k[0], (256, 512)).astype(jnp.bfloat16)
    phi = 0.05 * jax.random.normal(k[1], (512, 24))
    g = jax.random.normal(k[2], (256, 24))
    xf = x.astype(jnp.float32)
    if product == "forward":
        got = _split_dot(x, phi)
        exact, rough = (jnp.dot(xf, phi, precision=p)
                        for p in (HIGHEST, jax.lax.Precision.DEFAULT))
        rough = jnp.dot(x, phi.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    elif product == "d_phi":
        got = _split_dot(x.T, g)
        exact = jnp.dot(xf.T, g, precision=HIGHEST)
        rough = jnp.dot(x.T, g.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    else:
        got = _six_pair_dot(g, phi)
        exact = jnp.dot(g, phi.T, precision=HIGHEST)
        rough = jnp.dot(g.astype(jnp.bfloat16), phi.astype(jnp.bfloat16).T,
                        preferred_element_type=jnp.float32)
    scale = float(jnp.max(jnp.abs(exact)))
    assert float(jnp.max(jnp.abs(got - exact))) <= 4e-6 * scale
    assert float(jnp.max(jnp.abs(rough - exact))) >= 1e-4 * scale


def test_three_terms_are_the_float32_number():
    x = jax.random.normal(jax.random.PRNGKey(2), (4096,)) * jnp.exp(
        4 * jax.random.normal(jax.random.PRNGKey(3), (4096,)))
    hi, mid, lo = sm.split3(x)
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    total = (lo.astype(jnp.float32) + mid.astype(jnp.float32)) + hi.astype(
        jnp.float32)
    assert np.array_equal(np.asarray(total), np.asarray(x))


@pytest.mark.parametrize("T,C,dtype,reason", [
    (128, 192, jnp.bfloat16, "width_not_whole_lanes"),
    (200, 128, jnp.bfloat16, "tokens_not_whole_tiles"),
    (128, 128, jnp.float32, "streams_not_bfloat16"),
])
def test_refused_shapes_take_the_xla_form_and_say_so(T, C, dtype, reason):
    assert sm.plan(4, T, C, dtype) is None
    streams, y, phi, alpha, b = _inputs(1, T, C, dtype=dtype)
    hvd_trace.reset_build_ledger()
    h, post, res, carried = sm.pre(streams, phi, alpha, b, SPEC)
    out = sm.post(carried, y, post, res)
    want = _by_xla(streams, y, phi, alpha, b)
    assert np.array_equal(_f32(out), _f32(want[3]))
    records = hvd_trace.build_ledger()["fallbacks"]
    assert [(r["op"], r["reason"]) for r in records] == [
        ("hc_mix_pre", reason), ("hc_mix_post", reason)]
    assert records[0]["shape"] == {"streams": 4, "batch": 1, "seq": T,
                                   "width": C, "dtype": str(jnp.dtype(dtype))}


def test_plan_takes_the_largest_tile_that_divides_and_fits():
    assert sm.plan(4, 8192, 3584, jnp.bfloat16) in (128, 256)
    assert sm.plan(4, 384, 128, jnp.bfloat16) == 128
    assert sm.plan(4, 512, 128, jnp.bfloat16) == 256
    # a width whose 128-token tile passes the ceiling
    assert sm.plan(4, 8192, 128 * 1024, jnp.bfloat16) is None
    assert sm._refusal(4, 8192, 128 * 1024,
                       jnp.bfloat16) == "no_tile_fits_vmem"


def test_every_op_is_under_the_scope_hc_mix():
    """The forward's ops and the backward's all carry ``hc_mix`` in their
    ``op_name``, the backward's under ``transpose(``: what
    ``benchmark/scope_groups/xing4.json`` tells the two directions by."""
    args = _inputs(1, 128, 128)

    def loss(*a):
        h, post, res, out = _by_kernels(*a)
        return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.sum(
            h.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("(jit\(loss\)/[^"]+)"', text))
    mix = {p for p in paths if "hc_mix" in p}
    kernels = {p.rsplit("/", 2)[-2] for p in mix if p.endswith("pallas_call")}
    assert kernels == {"hc_mix_pre", "hc_mix_post", "hc_mix_post_bwd",
                       "hc_mix_pre_bwd"}
    for p in mix:
        backward = "_bwd/" in p
        assert ("transpose(" in p) or not backward, p
    # outside the scope: only the loss's own ops
    for p in paths - mix:
        assert re.match(r"jit\(loss\)/(transpose\()?jvp\(\)\)?/", p), p
    assert any("transpose(" in p and p.endswith("hc_mix_pre_bwd/pallas_call")
               for p in mix)
    assert any("transpose(" not in p and p.endswith("hc_mix_pre/pallas_call")
               for p in mix)
