"""The selective state-space scan (``ops/ssd.py``): the chunked form against
the rule token by token, values and all six gradients.

Tolerances. With float32 products the two are the same mathematics in another
order (a masked product and a pass over the chunks' states against one
rank-one update a token): gaps are float32 rounding, growing with the chunk
(a chunk of 128 sums 128 terms where the recurrence adds one): measured at
most 4e-6 of the largest value and 1.5e-4 of a gradient's largest element
(``dt``'s, which passes through two running sums), limits 2e-5 and 1e-3. With
bfloat16 products every operand of the four products is rounded to 2^-9
relative while decays and state stay float32: measured 0.5% of the largest
value and 1.2% of a gradient's norm, limits 2% and 5%, which a wrong mask, a
wrong group or a dropped chunk state exceeds by far.

The chunked form is two forms: batched XLA products at the shapes above (heads
of 8 over a state of 16 fill no lane: ``_plan`` refuses them, and the record
says why), and the Pallas kernels ``ssd_fwd`` / ``ssd_bwd`` where ``_plan``
takes the shapes (heads of 32 to 128 side by side in blocks of 128 lanes, a
state of whole lanes), interpreted on the CPU. The second half holds the
kernels against the rule and against the XLA form, values, final state and
all six gradients. In float32 the kernels and the XLA form are the same
products in the same order but for the sums inside a product: measured gaps
2.3e-7 of a value and 2.4e-5 of a gradient's largest element against the XLA
form (limits 2e-6 and 1e-4), and the rule's own bands against the rule.
Every form's values and gradients are computed as one compiled program each
(``_values``, ``_grads``), which is how a model calls them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import trace
from horovod_tpu.ops import ssd
from horovod_tpu.ops.ssd import (ssd_chunked, ssd_chunked_packed,
                                 ssd_recurrent)

b, T, H, P, G, N = 2, 80, 4, 8, 2, 16


def _wide(b, T, H, P, G, N, seed=0, dtype=jnp.float32):
    """x, dt (positive), A (negative), B, C and D from the seed; x, B and C
    in ``dtype``, as a model's mixer hands them over. C is drawn narrower
    over a wider state (as a state of 16 has it at 1), so that ``y`` and
    the readout's ``sin`` see values of one size at every width."""
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (arr(b, T, H, P).astype(dtype), jax.nn.softplus(arr(b, T, H)),
            -jnp.exp(arr(H)), arr(b, T, G, N).astype(dtype),
            (arr(b, T, G, N) * (16 / N) ** 0.5).astype(dtype), arr(H))


def _inputs(seed=0, heads=H, groups=G, length=T):
    return _wide(b, length, heads, P, groups, N, seed)


def _values(fn, args, **kw):
    # one compiled program, as a model calls it (operation by operation each
    # of the form's products, scans and kernels is compiled apart: 2 s a call)
    return jax.jit(lambda *a: fn(*a, **kw))(*args)


def _grads(fn, args, **kw):
    # a nonlinear readout of y and of the final state, so that every
    # cotangent differs; one compiled program (operation by operation the
    # scans' transposes take seconds)
    def readout(*a):
        y, S = fn(*a, **kw)
        return jnp.sum(jnp.sin(y)) + jnp.sum(S * S)
    # the program runs once: LLVM's expensive passes cost more than they save
    return jax.jit(jax.grad(readout, argnums=tuple(range(6)))).lower(
        *args).compile(compiler_options={
            "xla_llvm_disable_expensive_passes": True})(*args)


@functools.lru_cache(maxsize=None)
def _recurrent(seed, heads=H, groups=G):
    """``(args, (y, S), gradients)`` of the rule token by token, once a
    process."""
    args = _inputs(seed, heads, groups)
    return args, jax.jit(ssd_recurrent)(*args), _grads(ssd_recurrent, args)


@pytest.mark.parametrize("chunk,heads,groups", [
    (16, 4, 2), (32, 4, 2), (128, 4, 2), (16, 4, 4), (32, 6, 1)])
def test_chunked_equals_recurrent_in_float32(chunk, heads, groups):
    """Chunks that divide the 80 tokens (16), that do not (32: the sequence
    is padded with tokens of dt = 0) and that hold all of it (128); groups
    shared by 2, 1 and 6 heads."""
    args, (y0, S0), grads0 = _recurrent(0, heads, groups)
    y1, S1 = _values(ssd_chunked, args, chunk=chunk, dtype=jnp.float32)
    assert y1.shape == (b, T, heads, P) and y1.dtype == jnp.float32
    assert S1.shape == (b, heads, P, N)
    top = float(jnp.max(jnp.abs(y0)))
    assert float(jnp.max(jnp.abs(y1 - y0))) <= 2e-5 * top
    assert float(jnp.max(jnp.abs(S1 - S0))) <= 2e-5 * float(
        jnp.max(jnp.abs(S0)))
    names = ("x", "dt", "A", "B", "C", "D")
    for name, g0, g1 in zip(names, grads0,
                            _grads(ssd_chunked, args, chunk=chunk,
                                   dtype=jnp.float32)):
        assert g1.shape == g0.shape
        assert float(jnp.max(jnp.abs(g1 - g0))) <= 1e-3 * float(
            jnp.max(jnp.abs(g0))), name


def test_bfloat16_products_stay_inside_their_band():
    args, (y0, _), grads0 = _recurrent(3)
    y1, _ = _values(ssd_chunked, args, chunk=16)
    assert float(jnp.max(jnp.abs(y1 - y0))) <= 0.02 * float(
        jnp.max(jnp.abs(y0)))
    for g0, g1 in zip(grads0, _grads(ssd_chunked, args, chunk=16)):
        assert float(jnp.linalg.norm(g1 - g0)) <= 0.05 * float(
            jnp.linalg.norm(g0))


def test_a_head_that_forgets_within_a_chunk_is_exact():
    """``dt * A`` of -40 a token: ``exp`` of a chunk's running sum underflows
    float32 after three tokens. The mask is formed from differences, so
    nothing is divided by it and neighbours still see each other."""
    x, dt, A, B, C, D = _inputs(seed=5)
    A = jnp.full_like(A, -40.0)
    dt = jnp.ones_like(dt)
    y0, _ = jax.jit(ssd_recurrent)(x, dt, A, B, C, D)
    y1, _ = _values(ssd_chunked, (x, dt, A, B, C, D), chunk=16,
                    dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(y1)))
    np.testing.assert_allclose(y1, y0, atol=2e-5 * float(jnp.max(jnp.abs(y0))))
    g = _grads(ssd_chunked, (x, dt, A, B, C, D), chunk=16, dtype=jnp.float32)
    assert all(bool(jnp.all(jnp.isfinite(leaf))) for leaf in g)


def test_groups_are_read_and_not_repeated():
    """Head ``h`` reads group ``h // (H / G)``: with the groups' B and C
    repeated to the heads by hand the result is the same to the bit, and the
    lowered chunked form holds no ``[.., H, N]`` copy of B or C."""
    x, dt, A, B, C, D = _inputs(seed=7)
    kw = dict(chunk=16, dtype=jnp.float32)
    shared = _values(ssd_chunked, (x, dt, A, B, C, D), **kw)[0]
    rep = lambda m: jnp.repeat(m, H // G, axis=2)
    own = _values(ssd_chunked, (x, dt, A, rep(B), rep(C), D), **kw)[0]
    np.testing.assert_allclose(shared, own, atol=1e-5)
    text = jax.jit(lambda *a: ssd_chunked(*a, chunk=16)[0]).lower(
        x, dt, A, B, C, D).as_text()
    assert f"tensor<{b}x5x16x{H}x{N}x" not in text
    with pytest.raises(ValueError, match="share"):
        ssd_chunked(x, dt, A, B[:, :, :1].repeat(3, axis=2),
                    C[:, :, :1].repeat(3, axis=2), D)


def test_plan_notes():
    trace.reset_build_ledger()
    args = _inputs()
    jax.eval_shape(lambda *a: ssd_chunked(*a, chunk=32), *args)
    notes = trace.plan_args()
    assert {k: notes[k] for k in notes if k.startswith("ssm_")} == {
        "ssm_heads": H, "ssm_head_dim": P, "ssm_state": N, "ssm_groups": G,
        "ssm_chunk": 32, "ssm_chunks": 3, "ssm_kernel": False,
        "ssm_grid_steps": 0, "ssm_vmem_mb": 0.0}
    # heads of 8 fill no lane: the XLA form, and the record says so
    assert trace.build_ledger()["fallbacks"] == [{
        "op": "ssd_fwd", "reason": "heads_not_whole_lanes",
        "shape": {"batch": b, "seq": T, "heads": H, "head_dim": P,
                  "state": N, "groups": G, "chunk": 32}}]


# --------------------------------------------------------------------------
# The chunked form as Pallas kernels (interpreted here).

def _xla_form(monkeypatch, via, args, **kw):
    """``via`` (``_values`` or ``_grads``) of ``ssd_chunked`` with the plan
    refusing every shape."""
    with monkeypatch.context() as m:
        m.setattr(ssd, "_plan", lambda *a: None)
        return via(ssd_chunked, args, **kw)


def _uses_kernel(*args, **kw):
    return "pallas_call" in str(jax.make_jaxpr(
        lambda *a: ssd_chunked(*a, **kw))(*args))


# (sequences, T, heads, head width, groups, state, chunk)
KERNEL_CASES = {
    "eight_heads_a_group": (1, 48, 8, 64, 1, 128, 16),
    "two_heads_a_group_two_rows": (2, 64, 4, 64, 2, 128, 32),
    "one_head_a_group": (1, 64, 2, 128, 2, 128, 32),
    "four_heads_a_block": (1, 32, 8, 32, 2, 128, 16),
    "padded_last_chunk": (1, 40, 2, 64, 1, 128, 16),
    "one_chunk_holds_it_all": (1, 32, 2, 64, 1, 128, 128),
    "the_cell_s_chunk": (1, 256, 2, 64, 1, 128, 128),
    "state_of_two_lane_rows": (1, 32, 2, 64, 1, 256, 16),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_equals_recurrent_and_xla_form(case, monkeypatch):
    args = _wide(*KERNEL_CASES[case][:6], seed=11)
    kw = dict(chunk=KERNEL_CASES[case][6], dtype=jnp.float32)
    assert _uses_kernel(*args, **kw)
    rule = _values(ssd_recurrent, args)
    got = _values(ssd_chunked, args, **kw)
    xla = _xla_form(monkeypatch, _values, args, **kw)
    for name, a, r, x in zip(("y", "state"), got, rule, xla):
        assert a.shape == r.shape and a.dtype == jnp.float32, name
        top = float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(a - r))) <= 2e-5 * top, name
        assert float(jnp.max(jnp.abs(a - x))) <= 2e-6 * top, name


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_gradients_equal_recurrent_and_xla_form(case, monkeypatch):
    args = _wide(*KERNEL_CASES[case][:6], seed=12)
    kw = dict(chunk=KERNEL_CASES[case][6], dtype=jnp.float32)
    rule = _grads(ssd_recurrent, args)
    got = _grads(ssd_chunked, args, **kw)
    xla = _xla_form(monkeypatch, _grads, args, **kw)
    for name, a, r, x in zip(("x", "dt", "A", "B", "C", "D"), got, rule, xla):
        assert a.shape == r.shape, name
        top = float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(a - r))) <= 1e-3 * top, (
            name + " against the rule")
        assert float(jnp.max(jnp.abs(a - x))) <= 1e-4 * top, (
            name + " against the XLA form")


@pytest.mark.parametrize("case", ["eight_heads_a_group", "one_head_a_group",
                                  "padded_last_chunk"])
def test_kernel_in_bfloat16_rounds_where_the_xla_form_does(case, monkeypatch):
    """The model's call: bf16 x, B and C and bf16 MXU operands. Every product
    rounds the same operands at the same points in both forms, so values and
    state agree far inside bf16's own step; the gradients agree to a few of
    its steps (the kernel sums a group's heads into dB and dC in float32
    before one rounding, XLA's transposes multiply a float32 cotangent here
    on the CPU where the kernel rounds it as the chip's products do:
    measured at most 0.41% of a gradient's norm, dt's and A's 0.07%, limit
    1%), and both stay in the band round the rule (measured 3.6%)."""
    shape = KERNEL_CASES[case]
    args = _wide(*shape[:6], seed=13, dtype=jnp.bfloat16)
    kw = dict(chunk=shape[6])
    assert _uses_kernel(*args, **kw)
    rule_y, rule_s = _values(ssd_recurrent, args)
    rule_g = _grads(ssd_recurrent, args)
    got, got_g = _values(ssd_chunked, args, **kw), _grads(ssd_chunked, args,
                                                          **kw)
    xla = _xla_form(monkeypatch, _values, args, **kw)
    xla_g = _xla_form(monkeypatch, _grads, args, **kw)
    f32 = lambda a: a.astype(jnp.float32)
    top = lambda a: float(jnp.max(jnp.abs(f32(a))))
    norm = lambda a: float(jnp.linalg.norm(f32(a)))
    for a, r, x in zip(got, (rule_y, rule_s), xla):
        assert top(a - x) <= 1e-4 * top(r)
        assert top(a - r) <= 0.02 * top(r)
    for name, a, r, x in zip(("x", "dt", "A", "B", "C", "D"), got_g, rule_g,
                             xla_g):
        assert a.dtype == x.dtype and a.shape == x.shape, name
        assert norm(f32(a) - f32(x)) <= 0.01 * norm(r), (
            name + " against the XLA form")
        assert norm(f32(a) - f32(r)) <= 0.05 * norm(r), (
            name + " against the rule")


def test_a_head_that_forgets_within_a_chunk_is_exact_through_the_kernel():
    """As above at the kernels' widths: the mask inside the kernel is formed
    from differences masked before the exponential, and the state's factors
    ``exp(G_last - G)`` and ``exp(G)`` only ever shrink."""
    x, dt, A, B, C, D = _wide(1, 48, 2, 64, 1, 128, seed=5)
    A = jnp.full_like(A, -40.0)
    dt = jnp.ones_like(dt)
    kw = dict(chunk=16, dtype=jnp.float32)
    assert _uses_kernel(x, dt, A, B, C, D, **kw)
    y0, _ = jax.jit(ssd_recurrent)(x, dt, A, B, C, D)
    y1, _ = _values(ssd_chunked, (x, dt, A, B, C, D), **kw)
    assert bool(jnp.all(jnp.isfinite(y1)))
    np.testing.assert_allclose(y1, y0, atol=2e-5 * float(jnp.max(jnp.abs(y0))))
    g = _grads(ssd_chunked, (x, dt, A, B, C, D), **kw)
    assert all(bool(jnp.all(jnp.isfinite(leaf))) for leaf in g)


def test_plan_at_the_cell_s_shapes():
    """Nemotron-H's Mamba-2 layer at the cell's shapes: 64 heads of 64 in 8
    groups, a state of 128, chunks of 128, bf16 in: the kernels, a group's
    512 lanes a grid step, under the compiler's default scoped limit."""
    held = ssd._plan(128, 64, 128, 8, 2)
    assert held == ssd._step_vmem_bytes(128, 64, 128, 8, 2)
    assert 4 * 2 ** 20 < held <= ssd._VMEM_BUDGET
    trace.reset_build_ledger()
    aval = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype)
    f32 = jnp.float32
    jax.eval_shape(ssd_chunked, aval(2, 8192, 64, 64),
                   aval(2, 8192, 64, dtype=f32), aval(64, dtype=f32),
                   aval(2, 8192, 8, 128), aval(2, 8192, 8, 128),
                   aval(64, dtype=f32))
    notes = trace.plan_args()
    assert notes["ssm_kernel"] is True and notes["ssm_chunks"] == 64
    assert notes["ssm_grid_steps"] == 2 * 8 * 64
    assert notes["ssm_vmem_mb"] == round(held / 2 ** 20, 1)
    assert trace.build_ledger()["fallbacks"] == []
    # float32 in (the tests' call) and chunks of 256 still fit
    assert ssd._plan(128, 64, 128, 8, 4) is not None
    assert ssd._plan(256, 64, 128, 8, 2) is not None


# (T, heads, head width, groups, state, chunk) and the record's word
REFUSALS = {
    "chunk_no_power_of_two": ((96, 2, 64, 1, 128, 48),
                              "chunk_not_power_of_two"),
    "chunk_under_a_packed_register": ((32, 2, 64, 1, 128, 8),
                                      "chunk_not_power_of_two"),
    "head_width_divides_no_lane_row": ((32, 2, 96, 1, 128, 16),
                                       "heads_not_whole_lanes"),
    "head_wider_than_the_lanes": ((32, 1, 256, 1, 128, 16),
                                  "heads_not_whole_lanes"),
    "a_group_s_heads_fill_no_block": ((32, 2, 64, 2, 128, 16),
                                      "heads_not_whole_lanes"),
    "state_fills_no_lane_row": ((32, 2, 64, 1, 64, 16),
                                "state_not_whole_lanes"),
    "a_step_overruns_vmem": ((1024, 8, 64, 1, 128, 512),
                             "no_chunk_fits_vmem"),
}


@pytest.mark.parametrize("why", sorted(REFUSALS))
def test_refused_shapes_fall_back_with_their_record(why):
    (T, H, P, G, N, chunk), reason = REFUSALS[why]
    assert ssd._plan(min(chunk, T), P, N, H // G, 4) is None
    trace.reset_build_ledger()
    heavy = why == "a_step_overruns_vmem"
    args = _wide(1, T, H, P, G, N, seed=3)
    if heavy:   # shapes alone: the XLA form at this size is not this test's
        jax.eval_shape(lambda *a: ssd_chunked(*a, chunk=chunk), *args)
    else:
        assert not _uses_kernel(*args, chunk=chunk, dtype=jnp.float32)
    [record] = trace.build_ledger()["fallbacks"]
    assert record["op"] == "ssd_fwd" and record["reason"] == reason
    assert record["shape"] == {"batch": 1, "seq": T, "heads": H,
                               "head_dim": P, "state": N, "groups": G,
                               "chunk": min(chunk, T)}
    assert trace.plan_args()["ssm_kernel"] is False
    if not heavy:
        y, S = _values(ssd_chunked, args, chunk=chunk, dtype=jnp.float32)
        y0, S0 = jax.jit(ssd_recurrent)(*args)
        assert float(jnp.max(jnp.abs(y - y0))) <= 2e-5 * float(
            jnp.max(jnp.abs(y0)))
        assert float(jnp.max(jnp.abs(S - S0))) <= 2e-5 * float(
            jnp.max(jnp.abs(S0)))


@pytest.mark.parametrize("kernel", [True, False])
def test_plan_notes_are_recorded_either_way(kernel):
    assert not trace.ACTIVE
    trace.reset_build_ledger()
    args = _wide(1, 64, 4, 64 if kernel else 16, 2, 128)
    assert _uses_kernel(*args, chunk=16) == kernel
    notes = trace.plan_args()
    assert (notes["ssm_heads"], notes["ssm_groups"], notes["ssm_chunk"],
            notes["ssm_chunks"]) == (4, 2, 16, 4)
    assert notes["ssm_kernel"] is kernel
    assert notes["ssm_grid_steps"] == (1 * 2 * 4 if kernel else 0)
    assert (notes["ssm_vmem_mb"] > 0) == kernel
    assert len(trace.build_ledger()["fallbacks"]) == (0 if kernel else 1)



@pytest.mark.parametrize("form,shape", [
    ("kernels", (2, 48, 4, 64, 2, 128, 16)),
    ("kernels_padded", (1, 40, 2, 128, 2, 128, 16)),
    ("xla_form", (2, 48, 4, 8, 2, 16, 16)),
    ("b_starts_inside_a_block", (1, 32, 1, 64, 1, 128, 16)),
])
def test_packed_equals_separate(form, shape):
    """x, B and C side by side in one array, as the mixer's convolution
    leaves them: the kernels read the three where they lie (no slice of the
    array stands in front of the call), the cotangents come back as one
    array, and values and gradients are those of the three arrays handed
    over apart, to the bit. Where B does not start on a block of N lanes
    (one head of 64 in front of a state of 128) or the shapes are not the
    kernels', the slices go the old way."""
    b, T, H, P, G, N, chunk = shape
    x, dt, A, B, C, D = _wide(b, T, H, P, G, N, seed=21)
    xbc = jnp.concatenate([m.reshape(b, T, -1) for m in (x, B, C)], axis=-1)
    kw = dict(chunk=chunk, dtype=jnp.float32)
    packed = lambda xbc, dt, A, D: ssd_chunked_packed(
        xbc, dt, A, D, groups=G, state=N, **kw)

    def apart(xbc, dt, A, D):
        x, B, C = jnp.split(xbc, [H * P, H * P + G * N], axis=-1)
        return ssd_chunked(x.reshape(b, T, H, P), dt, A,
                           B.reshape(b, T, G, N), C.reshape(b, T, G, N), D,
                           **kw)

    text = str(jax.make_jaxpr(packed)(xbc, dt, A, D))
    assert ("pallas_call" in text) == form.startswith("kernels")
    # no slice of the array is cut out for the kernels
    assert ("split[" in text) == (not form.startswith("kernels"))
    assert f"f32[{b},{T},{H * P}] = slice" not in text
    def both(f):   # values and gradients, one compiled program
        def readout(*a):
            y, S = f(*a)
            return jnp.sum(jnp.sin(y)) + jnp.sum(S * S), (y, S)
        grads, values = jax.jit(jax.grad(
            readout, argnums=(0, 1, 2, 3), has_aux=True))(xbc, dt, A, D)
        return values + grads

    for got, want in zip(both(packed), both(apart)):
        assert got.shape == want.shape
        np.testing.assert_array_equal(got, want)


def test_packed_refuses_lanes_that_do_not_add_up():
    x, dt, A, B, C, D = _wide(1, 32, 4, 8, 2, 16, seed=1)
    xbc = jnp.concatenate([m.reshape(1, 32, -1) for m in (x, B, C)], axis=-1)
    with pytest.raises(ValueError, match="do not lie"):
        ssd_chunked_packed(xbc[..., :-2], dt, A, D, groups=2, state=16)
    with pytest.raises(ValueError, match="do not lie"):
        ssd_chunked_packed(xbc, dt[..., :3], A[:3], D[:3], groups=2, state=16)
