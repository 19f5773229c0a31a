"""Compile the main path's kernels and steps for a described TPU v5e.

The TPU's compiler is installed even where no chip is: it compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip's compiler would refuse (misaligned blocks, too much VMEM, a program
that does not fit 16 GB, a kernel that cannot be partitioned). A compile
that passes is not a chip run — ``chip_smoke.py`` is that — but it guards
every later PR at no chip time.

Everything that touches the topology lives in the module-scoped fixture:
only one process may load the TPU's library, so nothing here may run at
import, and the compiles happen in this process, all in this one file.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import horovod_tpu.jax as hvdj
from horovod_tpu.models.transformer import TransformerLM, make_gpt_loss_fn
from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.parallel import rules as R

# Full width, cut depth: the chip's compiler sees the real block shapes.
VOCAB, D_MODEL, HEADS, LAYERS, SEQ = 32768, 768, 12, 2, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip; keep it out.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture()
def compile_kernel(monkeypatch):
    """The process runs on the CPU backend, where ``interpret=None`` means
    "interpret"; these tests compile for the chip, so the kernel is."""
    monkeypatch.setattr(pa, "_resolve_interpret", lambda interpret: False)


def _compile(jitted, *avals):
    compiled = jitted.lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(topo, shape, dtype=jnp.bfloat16):
    """q, k and v of ``[bh, t, d]``, or ``[bh, t, d, d_v]`` for values of
    another width."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    arr = lambda width: jax.ShapeDtypeStruct(
        shape[:2] + (width,), dtype, sharding=one_chip)
    return arr(shape[2]), arr(shape[2]), arr(shape[-1])


@pytest.mark.parametrize("shape,stats", [
    ((96, 1024, 64), "f32[96,2,1,512]"),
    # The benchmark cells' own call: 8 sequences x 16 heads a chip.
    ((128, 1024, 64), "f32[128,2,1,512]"),
    # What the TP forward passes with few local heads.
    ((8, 1024, 64), "f32[8,2,1,512]"),
    # The Qwen3-Next cell's attention layer: 16 heads of width 256 over
    # one sequence of 8192 tokens (2 rows a grid step fit VMEM).
    ((16, 8192, 256), "f32[16,16,1,512]"),
    # The LFM2 cell's: 4 sequences x 32 heads of width 64 over 8192 tokens.
    ((128, 8192, 64), "f32[128,16,1,512]"),
    # The Xing4.0 cell's: 32 heads with keys of 192 and values of 128.
    ((32, 8192, 192, 128), "f32[32,16,1,512]"),
])
def test_flash_forward_compiles(topo, shape, stats):
    """The forward at the tiles the kernel picks from the shapes (a VMEM
    overrun or an unsupported layout fails here, not on the chip); the
    running max and sum leave it lane-dense."""
    fwd = functools.partial(pa.flash_attention, causal=True, interpret=False)
    compiled = _compile(jax.jit(fwd), *_qkv(topo, shape))
    assert stats in compiled.as_text()


@pytest.mark.parametrize("shape,kernels", [
    ((96, 1024, 64), 2),
    # The cells' own calls. GPT-2-medium's 8 sequences x 16 heads: the
    # forward and the one-pass backward, a row's whole dq in VMEM under the
    # compiler's default limit. The Qwen3-Next attention layer's 16 heads of
    # width 256 over 8192 tokens: a row's dq is 8 MB in f32 and as much in
    # the output block's buffers, one pass under the limit the call raises.
    ((128, 1024, 64), 2),
    ((16, 8192, 256), 2),
    # The LFM2 cell's attention layer (width 64 at 8192 tokens): two rows'
    # dq a step, 2 MB each in f32.
    ((128, 8192, 64), 2),
    # f32 operands: a whole dq fits beside 512 x 512 tiles over the default.
    ((8, 1024, 64, "float32"), 2),
    # The Xing4.0 cell's latent-attention layer: keys of 192, values of 128.
    ((32, 8192, 192, 128), 2),
    # The Keye-VL cell's shape without its selection: two rows' dq, 2 x 16 MB.
    ((32, 16384, 128), 2),
    # A row's dq is 32 MB in f32 alone: the dK/dV and the dQ kernel.
    ((16, 32768, 256), 3),
])
def test_flash_backward_compiles(topo, shape, kernels):
    """The backward at the tiles and the form ``_plan_bwd`` picks: the
    chip's compiler takes its VMEM (accumulators, the [Bq, Bk] f32
    temporaries, the whole-dq block, up to 43 MB a step under the scoped
    limit the call asks for, or the statistic columns), the transposed
    product and the row-to-column transposes, and the gradient holds
    kernels and no loop."""
    def loss(q, k, v):
        out = pa.flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    f32 = isinstance(shape[-1], str)
    compiled = _compile(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        *_qkv(topo, shape[:3] if f32 else shape,
              jnp.dtype(shape[3]) if f32 else jnp.bfloat16))
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == kernels
    assert " while(" not in text


@pytest.mark.parametrize("chunk,kernels", [(64, 2), (8, 0)])
def test_gated_delta_rule_compiles(topo, compile_kernel, chunk, kernels):
    """The chunked rule at the published head sizes (16 key heads shared by
    32 value heads of 128 x 128), forward and backward, on a quarter of the
    cell's sequence. Chunks of 64: the chunk-local part is the forward and
    the backward kernel (their VMEM, the [128, 128] transposes, the float32
    products at full precision and the lane-block index maps pass the chip's
    compiler) and the scan over chunks stays a loop. A chunk under the rows
    of a packed register: batched products and the scan, no kernel of ours."""
    from horovod_tpu.ops.gated_delta import gated_delta_chunked

    one_chip = SingleDeviceSharding(topo.devices[0])
    arr = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    T = 2048
    qk = arr((1, T, 16, 128), jnp.bfloat16)
    v = arr((1, T, 32, 128), jnp.bfloat16)
    gate = arr((1, T, 32), jnp.float32)

    def loss(q, k, v, g, beta):
        return gated_delta_chunked(q, k, v, g, beta, chunk=chunk)[0].sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, v, gate, gate).compile().as_text()
    assert "while" in text
    assert text.count("custom_call_target=\"tpu_custom_call\"") == kernels
    assert ("gdn_fwd" in text and "gdn_bwd" in text) == bool(kernels)


@pytest.mark.parametrize("chunk,heads,width,kernels", [
    (128, 64, 64, 2), (64, 64, 64, 2), (256, 64, 64, 2), (128, 32, 128, 2),
    (128, 64, 48, 0)])
def test_selective_scan_compiles(topo, compile_kernel, chunk, heads, width,
                                 kernels):
    """The chunked scan at the published sizes (64 heads of 64 in 8 groups, a
    state of 128, chunks of 128: the cell's call on a quarter of its
    sequence), forward and backward: the forward kernel keeping the chunks'
    states, the backward walking them from the last, and no loop left (the
    lane broadcasts of a head's column, the transposed products, the heads'
    sums through the MXU and the blocks of 16 lanes pass the chip's
    compiler).
    Chunks of 64 and 256 and heads of 128 too; heads of 48 divide no lane
    row: batched products and the scan over chunks, no kernel of ours."""
    from horovod_tpu.ops.ssd import ssd_chunked

    one_chip = SingleDeviceSharding(topo.devices[0])
    arr = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    T, f32 = 2048, jnp.float32

    def loss(x, dt, A, B, C, D):
        y, state = ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
        return y.sum() + state.sum()

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        arr((2, T, heads, width)), arr((2, T, heads), f32), arr((heads,), f32),
        arr((2, T, 8, 128)), arr((2, T, 8, 128)), arr((heads,), f32)
    ).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == kernels
    assert ("ssd_fwd" in text and "ssd_bwd" in text) == bool(kernels)
    assert ("while" in text) == (not kernels)


@pytest.mark.parametrize("tokens,top_k,total,held,width,parent_temp_gb", [
    # qwen3next-train-1chip: many experts, few rows
    (8192, 10, 512, 32, 512, 0.989),
    # lfm2moe-train-1chip: few experts, many rows
    (32768, 4, 32, 8, 1792, 4.133),
])
def test_dropless_expert_layer_compiles(topo, compile_kernel, tokens, top_k,
                                        total, held, width, parent_temp_gb):
    """The expert layer at the two cells' shapes (32 of 512 experts held,
    top-10, 2048 -> 512 -> 2048 on 8192 tokens; 8 of 32, top-4, 2048 -> 1792
    -> 2048 on 32768), forward and backward: the grouped products are the
    chip's own ragged-dot kernel, the per-token sums the gather-sum kernel
    (of a sum's gradient the backward alone is left: its first tile and the
    overflow tiles' loop body, a row count each), no [tokens, experts,
    capacity] tensor is in the program and no row buffer is scatter-added.
    The program's scratch is no more than at PR 38's tile rule
    (``parent_temp_gb``: the same compile there), and with a first tile
    sized to the load, the LFM2 cell's, about half of it (the cells' whole
    steps read 14.89 and 11.41 GB by ``memory_analysis()`` for 14.89 and
    13.52)."""
    from horovod_tpu.parallel.ep import dropless_moe

    one_chip = SingleDeviceSharding(topo.devices[0])
    arr = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    loss = lambda *a: dropless_moe(*a, top_k=top_k).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arr((tokens, 2048), jnp.bfloat16), arr((2048, total)),
        arr((held, 2048, width)), arr((held, 2048, width)),
        arr((held, width, 2048)),
    ).compile()
    text = compiled.as_text()
    calls = [l for l in text.splitlines()
             if "custom-call(" in l and "tpu_custom_call" in l]
    assert sum("moe_combine" in l for l in calls) == 2
    assert f"[{tokens},{total}," not in text.replace(" ", "")  # x capacity
    scattered = [l for l in text.splitlines() if " scatter(" in l
                 and ",2048]" in l.split(" scatter(")[0]]
    assert not scattered
    assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp_gb * 1e9


def test_stream_mix_kernels_compile(topo, compile_kernel):
    """One stream mix at the Xing4.0 cell's shape (four bfloat16 streams of
    8192 tokens x 3584), forward and backward: the four kernels pass the
    chip's compiler at the tile and under the scoped VMEM limit that
    ``plan`` counts for them (the transposes of the maps' planes, the
    sublane slices of four, the contractions of 128 + 32 and of 128 tokens,
    the cotangent accumulated in place), no product at ``HIGHEST`` is left
    in the program and ``phi``'s gradient is float32."""
    from horovod_tpu import trace as hvd_trace
    from horovod_tpu.ops import stream_mix as sm

    n, B, T, C = 4, 1, 8192, 3584
    one_chip = SingleDeviceSharding(topo.devices[0])
    arr = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    spec = sm.Spec(1e-6, 1e-6, (-30.0, 30.0), 20)
    tile = sm.plan(n, T, C, jnp.bfloat16)
    assert tile and sm._tile_bytes(n, tile, C) <= sm._VMEM_CEILING

    def loss(streams, y, phi, alpha, b):
        h, post, res, carried = sm.pre(streams, phi, alpha, b, spec)
        out = sm.post(carried, y + h, post, res)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    hvd_trace.reset_build_ledger()
    compiled = _compile(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))),
        arr((n, B, T, C), jnp.bfloat16), arr((B, T, C), jnp.bfloat16),
        arr((n * C, 24)), arr((3,)), arr((24,)))
    assert hvd_trace.build_ledger()["fallbacks"] == []
    assert hvd_trace.plan_args()["hc_mix_tile"] == tile
    text = compiled.as_text()
    calls = [l for l in text.splitlines()
             if "custom-call(" in l and "tpu_custom_call" in l]
    for name in ("hc_mix_pre", "hc_mix_post", "hc_mix_post_bwd",
                 "hc_mix_pre_bwd"):
        assert sum(f"/{name}/" in l for l in calls) == 1, name
    assert "f32[14336,24]" in text.replace(" ", "")       # d_phi
    assert "algorithm=dot_bf16_bf16_f32_x6" not in text
    assert "operand_precision={highest" not in text
    # the streams' float32 copies are gone from the mix (the loss has its own)
    assert not [l for l in text.splitlines() if "hc_mix" in l
                and "f32[4,1,8192,3584]" in l.replace(" ", "")]


def test_ring_block_compiles(topo):
    """One ring step of T=1024 over four ranks: [B*H, T/4, D] blocks and a
    traced offset, forward and backward."""
    def loss(q, k, v, delta):
        o, m, l = pa.flash_attention_block(
            q, k, v, delta, sm_scale=0.125, interpret=False
        )
        return o.sum() + m.sum() + l.sum()

    delta = jax.ShapeDtypeStruct(
        (), jnp.float32, sharding=SingleDeviceSharding(topo.devices[0])
    )
    _compile(
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))),
        *_qkv(topo, (96, 256, 64)), delta,
    )


def _lm_shapes(batch):
    model = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL, n_heads=HEADS,
                          n_layers=LAYERS, max_len=SEQ)
    params = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32)
        )["params"]
    )
    tokens = jax.ShapeDtypeStruct((batch, SEQ), jnp.int32)
    return model, params, (tokens, tokens)


def _placed(tree, mesh, specs):
    return jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec or P())
        ),
        tree, specs,
    )


def test_lm_step_compiles_on_one_chip(topo, compile_kernel):
    model, params, batch = _lm_shapes(batch=8)
    mesh = hvdj.build_mesh({"data": 1}, devices=topo.devices[:1])
    tx = optax.adamw(3e-4)

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b[0])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b[1]
        ).mean()

    step = hvdj.make_train_step(loss_fn, tx, mesh)
    state = jax.eval_shape(tx.init, params)
    rep = lambda tree: jax.tree.map(lambda _: P(), tree)
    compiled = _compile(
        step,
        _placed(params, mesh, rep(params)),
        _placed(state, mesh, rep(state)),
        _placed(batch, mesh, jax.tree.map(lambda _: P("data"), batch)),
    )
    assert compiled.as_text().count("tpu_custom_call") >= LAYERS


def test_composed_step_compiles_on_2x2(topo, compile_kernel):
    """``rules="gpt"`` DP2 x TP2 on the described mesh: the kernel
    partitions under shard_map and both axes' collectives are there."""
    _, params, batch = _lm_shapes(batch=8)
    mesh = hvdj.build_mesh({"data": 2, "model": 2}, devices=topo.devices)
    tx = optax.adamw(3e-4)
    step = hvdj.make_train_step(
        make_gpt_loss_fn(HEADS, model_axis="model"), tx, mesh, rules="gpt"
    )
    state = jax.eval_shape(tx.init, params)
    # The composed builder builds on its first call; shapes are enough.
    jax.eval_shape(step, params, state, batch)
    compiled = _compile(
        step.jitted,
        _placed(params, mesh, R.match_partition_rules("gpt", params)),
        _placed(state, mesh, R.match_partition_rules("gpt", state)),
        _placed(batch, mesh, jax.tree.map(lambda _: P("data"), batch)),
    )
    assert "all-reduce" in compiled.as_text()


def _compile_cell_step(topo, name, shape):
    """A benchmark cell's whole step (``hvd.make_train_step`` over the
    family's model at the configuration's sizes and the traffic's ``(seq_len,
    per_chip_batch)``, which has to be ``shape``) compiled for the described
    chip from shapes alone; the build ledger holds that compile's notes."""
    import os
    import sys

    from horovod_tpu import trace as hvd_trace

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import manifest, weights

    cell = manifest.Cell(manifest.load_manifest(), name)
    cfg, traffic = cell.config, cell.traffic
    seq, batch = shape
    assert (traffic["seq_len"], traffic["per_chip_batch"]) == shape
    mesh = hvdj.build_mesh({"data": 1}, devices=topo.devices[:1])
    rep, dat = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32, sharding=rep),
        cell.family.param_spec(cfg), is_leaf=weights.is_leaf)
    step, tx = cell.family.build_train(cfg, traffic, {}, mesh)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(tx.init, params))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=dat)
    hvd_trace.reset_build_ledger()
    return cell, _compile(step, params, state, (tokens, tokens))


def test_xing4_step_compiles_under_16_gb(topo, compile_kernel):
    """The Xing4.0 cell's whole step (``hvd.make_train_step`` over
    ``Xing4LM`` at the configuration's sizes: 759.3 M parameters, one
    8192-token sequence) for the described chip: the flash kernels at 192 /
    128 are in it, one forward and one backward a layer (the layer's
    recomputation keeps the forward's named result; a head's whole dq is
    held in VMEM), and parameters, AdamW's moments, gradients and
    scratch come to no more than 16.0 GB by the compiler's own count."""
    from horovod_tpu import trace as hvd_trace

    cell, compiled = _compile_cell_step(topo, "xing4-train-1chip", (8192, 1))
    cfg = cell.config
    layers = cfg["num_hidden_layers"]
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") >= 4 * layers
    assert sum("flash_bwd" in l for l in text.splitlines()
               if "custom-call(" in l and "tpu_custom_call" in l) == layers
    # the sparse layers' per-token sums are the gather-sum kernel at this
    # model's 28 sublanes a row, and no call site fell back to XLA
    assert sum("moe_combine" in l for l in text.splitlines()
               if "custom-call(" in l) >= 3 * (layers - 1)
    assert hvd_trace.build_ledger()["fallbacks"] == []
    # ten stream mixes: both halves forward, `pre` again under recomputation
    # and both transposed, by the kernels
    mixes = [l for l in text.splitlines()
             if "custom-call(" in l and "/hc_mix/" in l]
    assert len(mixes) >= 5 * 2 * layers
    assert hvd_trace.plan_args()["hc_mix_tile"] == 128
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 12.1e9 < held <= 16.0e9, held


def test_sparse_selection_kernels_compile(topo, compile_kernel):
    """The Keye-VL cell's four kernels at its shape (one 16384-token
    sequence, 32 query / 4 key-value heads of 128, an indexer of 16 heads of
    64 over one key head, top 2048) pass the chip's compiler: the selection
    (int8 mask stores, ordered int32 keys in 8 MB of VMEM scratch, dynamic
    loops over the causal chunks, a scoped limit over Mosaic's default), the
    flash kernels under the selection (an int8 tile and a scalar-prefetched
    table, the forward and the ONE backward kernel, two heads' whole dq in 45
    MB of VMEM a step), and the objective with its gradient
    (all 32 heads inside a grid step, the keys' whole gradient resident)."""
    from horovod_tpu import trace as hvd_trace
    from horovod_tpu.ops import sparse_index as si

    B, T, H, KV, D, J, Di, K = 1, 16384, 32, 4, 128, 16, 64, 2048
    one_chip = SingleDeviceSharding(topo.devices[0])
    bf16 = jnp.bfloat16
    arr = lambda shape, dtype=bf16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def loss(x, kv, q_i, k_i, w):
        q, k, v = x, kv, kv
        selection, lse_i = si.select_top_k(q_i, k_i, w, top_k=K)
        heads_first = lambda a: a.transpose(0, 2, 1, 3)
        kr, vr = (heads_first(jnp.repeat(a, H // KV, axis=2)) for a in (k, v))
        out, lse = pa.flash_attention(heads_first(q), kr, vr,
                                      selection=selection)
        kl = si.index_kl(jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
                         lse, selection, q_i, k_i, w, lse_i,
                         sm_scale=D ** -0.5)
        return jnp.sum(out.astype(jnp.float32)) + kl

    hvd_trace.reset_build_ledger()
    compiled = _compile(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))),
        arr((B, T, H, D)), arr((B, T, KV, D)), arr((B, T, J, Di)),
        arr((B, T, Di)), arr((B, T, J), jnp.float32))
    assert hvd_trace.build_ledger()["fallbacks"] == []
    notes = hvd_trace.plan_args()
    assert notes["sparse_index_kernel"] and notes["sparse_index_loss_kernel"]
    assert notes["flash_selection"] and notes["flash_rows_per_step"] == 2
    assert (notes["flash_bwd_rows_per_step"], notes["flash_bwd_one_pass"],
            notes["flash_bwd_vmem_mb"]) == (2, True, 44.6)
    text = compiled.as_text()
    calls = [l for l in text.splitlines()
             if "custom-call(" in l and "tpu_custom_call" in l]
    for name, count in (("sparse_index_select", 1), ("sparse_index_kl", 1),
                        ("flash_bwd", 1)):
        assert sum(name in l for l in calls) == count, name
    assert len(calls) == 4
    # no float32 [T, T] stands in HBM; the selection is int8
    flat = text.replace(" ", "")
    assert "f32[1,16384,16384]" not in flat and "s8[1,16384,16384]" in flat


def test_nemotron_h_step_compiles_under_15_gb(topo, compile_kernel):
    """The Nemotron-H cell's whole step (``hvd.make_train_step`` over
    ``NemotronHLM`` at the configuration's sizes: 667.0 M parameters, two
    8192-token sequences) for the described chip: the flash kernels at
    width 128 over 64 rows are in it, one forward and one backward for the
    one attention layer; the four selective scans are the kernels ``ssd_fwd``
    (the first pass and the layer's recomputation) and ``ssd_bwd``, with no
    fallback; and parameters, AdamW's moments, gradients and scratch come to
    no more than the 15.0 GB that let the cell take two sequences (12.0 by
    the compiler's own count; 14.8 when the scans were XLA and held the mask
    of decays under a checkpoint of their own)."""
    from horovod_tpu import trace as hvd_trace

    _, compiled = _compile_cell_step(topo, "nemotronh-train-1chip", (8192, 2))
    calls = [l for l in compiled.as_text().splitlines()
             if "custom-call(" in l and "tpu_custom_call" in l]
    assert sum("flash_bwd" in l for l in calls) == 1
    assert sum("/ssd_fwd/" in l for l in calls) == 2 * 4
    assert sum("/ssd_bwd/" in l for l in calls) == 4
    # the four expert layers' per-token sums are the gather-sum kernel
    assert sum("moe_combine" in l for l in calls) >= 3 * 4
    assert hvd_trace.build_ledger()["fallbacks"] == []
    notes = hvd_trace.plan_args()
    assert (notes["ssm_heads"], notes["ssm_head_dim"], notes["ssm_state"],
            notes["ssm_groups"], notes["ssm_chunk"], notes["ssm_chunks"],
            notes["ssm_kernel"], notes["ssm_grid_steps"]) == (
        64, 64, 128, 8, 128, 64, True, 1024)
    assert notes["moe_gated"] is False and notes["moe_tile_rows"] == 7680
    assert (notes["flash_rows_per_step"], notes["flash_grid_steps"],
            notes["flash_bwd_rows_per_step"], notes["flash_bwd_one_pass"]) == (
        4, 4096, 2, True)
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 11.5e9 < held <= 12.5e9, held


def test_ling_step_compiles_under_16_3_gb(topo, compile_kernel):
    """The Ling-3.0 cell's whole step (``hvd.make_train_step`` over ``LingLM``
    at the configuration's sizes: 884.5 M parameters, 14.15 GB of state
    before a token is seen) for the described chip, at the length the
    cell's memory rule chose: 4096 tokens (at 8192 the compiler's own count
    is 17.64 GB, past the 16.3 the rule allows). The flash kernels at 192 /
    128 are in it once each for the one latent-attention layer, the six
    delta rules are XLA in four blocks of 1024 tokens with no fallback, the
    choice is group-limited, and parameters, AdamW's moments, gradients and
    scratch come to no more than 16.3 GB."""
    from horovod_tpu import trace as hvd_trace

    _, compiled = _compile_cell_step(topo, "ling3-train-1chip", (4096, 1))
    calls = [l for l in compiled.as_text().splitlines()
             if "custom-call(" in l and "tpu_custom_call" in l]
    assert sum("flash_bwd" in l for l in calls) == 1
    assert sum("moe_combine" in l for l in calls) >= 3 * 6
    assert hvd_trace.build_ledger()["fallbacks"] == []
    notes = hvd_trace.plan_args()
    assert (notes["kda_chunk"], notes["kda_sub_block"], notes["kda_heads"],
            notes["kda_chunks"], notes["kda_padded_tokens"],
            notes["kda_local_blocks"]) == (64, 16, 32, 64, 0, 4)
    assert (notes["attn_qk_width"], notes["attn_v_width"]) == (192, 128)
    assert (notes["moe_experts_total"], notes["moe_experts_held"],
            notes["moe_top_k"], notes["moe_groups"],
            notes["moe_groups_kept"]) == (512, 8, 8, 8, 4)
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 15.0e9 < held <= 16.3e9, held
