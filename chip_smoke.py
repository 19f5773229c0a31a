#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py             # one chip: train_lm, train_resnet, serve, eager
    python chip_smoke.py --chips 4   # four chips: DP4 / DP2xTP2 / fused, vs one chip

It drives the three entry points a user reaches first — ``hvd.make_train_step``,
``hvd.serve()`` and ``hvd.init()`` + ``hvd.allreduce`` — at the full width of the
models the repo ships (depth as shipped too; weights random, from ``--seed``),
checks what comes out by the repo's own means, and fails the moment a phase
fails: no phase's exception is caught. It never picks a platform: it fails
unless JAX's default device is a TPU. One process owns the chip, so everything
runs in this process and nothing that needs the chip is started as a child.

Every phase prints one JSON line. The seconds on those lines are smoke
readings of a cold run, not benchmark results. The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

# The shipped full sizes (a GPT-2-small-class LM, the ResNet-50
# configuration of the one old chip record). Tests pass smaller ones.
LM = dict(d_model=768, n_heads=12, n_layers=12, vocab=32768, seq=1024,
          batch=8, steps=5)
RESNET = dict(model="resnet50", classes=1000, image=224, batch=32, steps=5)
SERVE = dict(requests=4, prompt_min=16, prompt_max=64, max_tokens=16)
EAGER = dict(elements=1 << 20)  # 4 MB of f32
FOUR = dict(steps=3, global_batch=32)

# bf16 activations: two formulations of the same step agree to about three
# significant digits in the loss.
LOSS_RTOL = 1e-2


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_tpu(count):
    """The device gate: this script reports on the chip or on nothing."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's default device is {devices[0].platform!r}, "
            "not a TPU; nothing to report"
        )
    if len(devices) < count:
        raise SystemExit(
            f"chip_smoke: --chips {count} needs {count} devices, "
            f"found {len(devices)}"
        )
    return devices[:count]


def check_kernel_in_program(text, what):
    """The flash kernel must be IN the compiled program — neither
    interpreted nor replaced by the dense fallback."""
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled step")


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _avals(tree):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree,
    )


def _run_steps(step, params, state, batch, steps):
    """``steps`` calls on one fixed batch. Returns the final params/state,
    the losses and each call's seconds (the first includes the compile)."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))  # waits for the device
        secs.append(time.perf_counter() - t0)
    return params, state, losses, secs


def _timing(secs):
    steady = statistics.median(secs[1:]) if len(secs) > 1 else 0.0
    return dict(
        seconds=round(sum(secs), 3),
        compile_seconds=round(max(secs[0] - steady, 0.0), 3),
        smoke_step_seconds=round(steady, 4),
    )


def _check_losses(losses, what):
    import math

    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")


def _close(a, b, what):
    if abs(a - b) > LOSS_RTOL * max(abs(a), abs(b)):
        raise AssertionError(f"{what}: {a} vs {b} (rtol {LOSS_RTOL})")


# --------------------------------------------------------------- the LM


def lm_setup(lm, seed, global_batch):
    """Model, host copy of the seeded params, and one fixed seeded batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=lm["vocab"], d_model=lm["d_model"], n_heads=lm["n_heads"],
        n_layers=lm["n_layers"], max_len=lm["seq"],
    )
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, lm["vocab"], (global_batch, lm["seq"]))
    labels = rng.randint(0, lm["vocab"], (global_batch, lm["seq"]))
    params = jax.device_get(jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, lm["seq"]), jnp.int32)
    )["params"])
    batch = (tokens.astype(np.int32), labels.astype(np.int32))
    return model, params, batch


def _flax_lm_loss(model):
    import optax

    def loss_fn(p, batch):
        tokens, labels = batch
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    return loss_fn


def _lm_optimizer():
    import optax

    import horovod_tpu.jax as hvd

    return hvd.DistributedOptimizer(optax.adamw(3e-4))


def run_plain_lm(model, params, batch, mesh, steps):
    """``make_train_step`` data-parallel over ``mesh`` (flax forward)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu.jax as hvd

    tx = _lm_optimizer()
    step = hvd.make_train_step(_flax_lm_loss(model), tx, mesh)
    params = hvd.broadcast_variables(params, mesh)
    state = hvd.broadcast_variables(tx.init(params), mesh)
    batch = jax.device_put(batch, NamedSharding(mesh, P("data")))
    placed = (params, state, batch)
    args = _avals(placed)
    params, state, losses, secs = _run_steps(step, *placed, steps)
    text = step.lower(*args).compile().as_text()
    return dict(losses=losses, secs=secs, text=text, params=params,
                state=state, batch=batch)


def run_composed_lm(lm, params, batch, mesh, steps, tp_overlap=None):
    """``make_train_step(rules="gpt")`` on a (data, model) mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu.jax as hvd
    from horovod_tpu.models.transformer import make_gpt_loss_fn
    from horovod_tpu.parallel import rules as R

    tx = _lm_optimizer()
    step = hvd.make_train_step(
        make_gpt_loss_fn(lm["n_heads"], model_axis="model"), tx, mesh,
        rules="gpt", tp_overlap=tp_overlap,
    )
    specs = R.match_partition_rules("gpt", params)
    params = R.shard_tree(params, specs, mesh)
    state = tx.init(params)
    state = R.shard_tree(
        state, R.match_partition_rules("gpt", state), mesh
    )
    batch = jax.device_put(batch, NamedSharding(mesh, P("data")))
    placed = (params, state, batch)
    args = _avals(placed)
    params, state, losses, secs = _run_steps(step, *placed, steps)
    lowered = step.jitted.lower(*args)
    return dict(losses=losses, secs=secs, text=lowered.compile().as_text(),
                hlo=lowered.compiler_ir(dialect="hlo").as_hlo_text(),
                params=params, state=state, batch=batch,
                specs=step.sharding_specs)


def dense_reference_loss(lm, params, batch):
    """First-step loss of the same model with dense attention in place of
    the flash kernel (forward only, on the default device)."""
    import jax

    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.ops.pallas_attention import _dense_full

    def dense_bthd(q, k, v):
        B, T, H, D = q.shape
        fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, D)
        out = _dense_full(fold(q), fold(k), fold(v), True, D ** -0.5)
        return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)

    model = TransformerLM(
        vocab_size=lm["vocab"], d_model=lm["d_model"], n_heads=lm["n_heads"],
        n_layers=lm["n_layers"], max_len=lm["seq"], attn_fn=dense_bthd,
    )
    return float(jax.jit(_flax_lm_loss(model))(params, batch))


def phase_train_lm(lm, seed, devices):
    import horovod_tpu.jax as hvd

    model, params, batch = lm_setup(lm, seed, lm["batch"])
    plain = run_plain_lm(
        model, params, batch, hvd.build_mesh(devices=devices), lm["steps"]
    )
    _check_losses(plain["losses"], "train_lm plain")
    check_kernel_in_program(plain["text"], "train_lm plain")

    composed = run_composed_lm(
        lm, params, batch,
        hvd.build_mesh({"data": 1, "model": 1}, devices=devices),
        lm["steps"],
    )
    _check_losses(composed["losses"], "train_lm rules=gpt")
    check_kernel_in_program(composed["text"], "train_lm rules=gpt")
    _close(plain["losses"][0], composed["losses"][0],
           "first-step loss, plain vs rules=gpt")

    dense = dense_reference_loss(lm, params, batch)
    _close(plain["losses"][0], dense, "first-step loss, flash vs dense")
    emit(
        "train_lm", **lm,
        plain=dict(losses=plain["losses"], **_timing(plain["secs"])),
        rules_gpt=dict(losses=composed["losses"],
                       **_timing(composed["secs"])),
        dense_attention_first_loss=dense,
        checked=["losses finite and falling", "plain == rules=gpt (step 1)",
                 "flash == dense attention (step 1)",
                 "tpu_custom_call in both compiled steps"],
    )
    return params


# ------------------------------------------------------------- ResNet-50


def phase_train_resnet(cfg, seed, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu.jax as hvd
    from horovod_tpu.models import get_model

    mesh = hvd.build_mesh(devices=devices)
    model = get_model(cfg["model"], num_classes=cfg["classes"])
    rng = np.random.RandomState(seed)
    images = rng.randn(
        cfg["batch"], cfg["image"], cfg["image"], 3
    ).astype(np.float32)
    labels = rng.randint(0, cfg["classes"], (cfg["batch"],)).astype(np.int32)
    variables = jax.jit(functools.partial(model.init, train=False))(
        jax.random.PRNGKey(seed), jnp.asarray(images[:2])
    )
    batch_stats = variables["batch_stats"]

    def loss_fn(p, batch):
        x, y = batch
        # Train-mode BatchNorm normalizes with the batch's own statistics;
        # the running averages it would update are not read by a train
        # step, so the smoke does not carry them.
        logits, _ = model.apply(
            {"params": p, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    step = hvd.make_train_step(loss_fn, tx, mesh)
    params = hvd.broadcast_variables(variables["params"], mesh)
    batch = jax.device_put(
        (images, labels), NamedSharding(mesh, P("data"))
    )
    state = hvd.broadcast_variables(tx.init(params), mesh)
    _, _, losses, secs = _run_steps(step, params, state, batch, cfg["steps"])
    _check_losses(losses, "train_resnet")
    emit("train_resnet", **cfg, losses=losses, **_timing(secs),
         checked=["losses finite and falling"])


# ----------------------------------------------------------------- serve


def _post(port, path, body=None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"} if data else {},
    )
    with urllib.request.urlopen(req, timeout=300) as resp:
        return resp.status, resp.read().decode()


def reference_greedy(lm, params, prompts, max_tokens):
    """Greedy decoding by full recompute with the flax forward: every new
    token re-runs the whole (causal) model over a fixed padded length, so
    one program serves all requests and positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=lm["vocab"], d_model=lm["d_model"], n_heads=lm["n_heads"],
        n_layers=lm["n_layers"], max_len=lm["seq"], dtype=jnp.float32,
    )
    pad = 128 * -(-(max(map(len, prompts)) + max_tokens) // 128)
    pad = min(pad, lm["seq"])
    forward = jax.jit(lambda p, t: model.apply({"params": p}, t))
    seqs = np.zeros((len(prompts), pad), np.int32)
    lens = np.array([len(p) for p in prompts])
    for i, p in enumerate(prompts):
        seqs[i, :len(p)] = p
    rows = np.arange(len(prompts))
    for _ in range(max_tokens):
        logits = forward(params, jnp.asarray(seqs))
        seqs[rows, lens] = np.asarray(
            jnp.argmax(logits[rows, lens - 1], axis=-1)
        )
        lens = lens + 1
    return [
        [int(t) for t in seqs[i, len(p):len(p) + max_tokens]]
        for i, p in enumerate(prompts)
    ]


def serve_prompts(lm, cfg, seed):
    import numpy as np

    rng = np.random.RandomState(seed + 1)
    return [
        [int(t) for t in rng.randint(
            0, lm["vocab"],
            size=rng.randint(cfg["prompt_min"], cfg["prompt_max"] + 1),
        )]
        for _ in range(cfg["requests"])
    ]


def serve_completions(lm, cfg, params, prompts):
    """``hvd.serve(..., http=True)``, every prompt POSTed at once, then
    /healthz and /metrics, then a clean stop. Returns the completions."""
    import horovod_tpu as hvd

    handle = hvd.serve(params, n_heads=lm["n_heads"], http=True)
    try:
        # All at once, so the continuous batcher has a batch to form.
        with ThreadPoolExecutor(len(prompts)) as pool:
            futures = [
                pool.submit(
                    _post, handle.port, "/v1/completions",
                    {"prompt": p, "max_tokens": cfg["max_tokens"]},
                )
                for p in prompts
            ]
            replies = [f.result() for f in futures]
        health = _post(handle.port, "/healthz")
        metrics = _post(handle.port, "/metrics")
    finally:
        handle.stop()
    served = []
    for status, body in replies:
        if status != 200:
            raise AssertionError(f"serve: HTTP {status}: {body[:200]}")
        served.append([int(t) for t in json.loads(body)["completion"]])
    if health[0] != 200 or metrics[0] != 200:
        raise AssertionError(f"serve: healthz {health[0]}, metrics {metrics[0]}")
    if json.loads(health[1])["replicas"] < 1:
        raise AssertionError(f"serve: healthz says {health[1]}")
    if handle.engine.live_replicas() != 0:
        raise AssertionError("serve: the engine did not stop cleanly")
    return served


def phase_serve(lm, cfg, seed, params):
    prompts = serve_prompts(lm, cfg, seed)
    t0 = time.perf_counter()
    served = serve_completions(lm, cfg, params, prompts)
    seconds = time.perf_counter() - t0
    expect = reference_greedy(lm, params, prompts, cfg["max_tokens"])
    for i, (got, want) in enumerate(zip(served, expect)):
        if len(got) != cfg["max_tokens"] or got != want:
            raise AssertionError(
                f"serve: request {i} (prompt {len(prompts[i])} tokens) "
                f"diverged from full-recompute greedy: {got} vs {want}"
            )
    emit("serve", **cfg, prompt_tokens=[len(p) for p in prompts],
         seconds=round(seconds, 3),
         checked=[f"{len(prompts)} completions over HTTP",
                  "healthz and metrics answer",
                  "tokens == full-recompute greedy (flax forward), both "
                  "at JAX's default matmul precision",
                  "engine stopped"])


# ----------------------------------------------------------------- eager


def phase_eager(cfg, started):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    t0 = time.perf_counter()
    hvd.init()
    try:
        runtime = type(hvd._rt()).__name__
        x = jnp.arange(cfg["elements"], dtype=jnp.float32)
        summed = hvd.allreduce(x, name="chip_smoke/allreduce", op=hvd.Sum)
        sent = hvd.broadcast(x, 0, name="chip_smoke/broadcast")
        np.testing.assert_array_equal(np.asarray(summed), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(sent), np.asarray(x))
        on = {d.platform for r in (summed, sent) for d in r.devices()}
    finally:
        hvd.shutdown()
    if runtime != "NativeRuntime":
        raise AssertionError(f"eager: runtime is {runtime}, not the native core")
    lib = basics._LIB_PATH
    src_dir = os.path.join(os.path.dirname(lib), "src")
    newest_src = max(
        os.path.getmtime(os.path.join(src_dir, f))
        for f in os.listdir(src_dir) if f.endswith(".cc")
    )
    if os.path.getmtime(lib) < newest_src:
        raise AssertionError("eager: libhvd_core.so is older than cpp/src")
    if on != {jax.devices()[0].platform}:
        raise AssertionError(f"eager: results live on {on}")
    emit("eager", bytes=cfg["elements"] * 4, runtime=runtime,
         core_built_this_run=os.path.getmtime(lib) >= started,
         result_platform=sorted(on), seconds=round(time.perf_counter() - t0, 3),
         checked=["native core up to date with cpp/src",
                  "allreduce and broadcast exact", "results on the device"])


# ------------------------------------------------------------ four chips


def _expected_shard_shape(shape, spec, mesh):
    out = list(shape)
    for dim, axes in enumerate(tuple(spec)):
        if axes is None:
            continue
        for ax in (axes if isinstance(axes, tuple) else (axes,)):
            out[dim] //= mesh.shape[ax]
    return tuple(out)


def _check_placement(tree, specs, mesh, what):
    """Every leaf has one addressable shard on EACH device of the mesh, of
    the shape its PartitionSpec says."""
    import jax
    from jax.sharding import PartitionSpec as P

    devices = set(mesh.devices.flat)
    leaves = jax.tree.leaves(tree)
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, P) or s is None
    ) if not isinstance(specs, P) else [specs] * len(leaves)
    if len(spec_leaves) != len(leaves):
        raise AssertionError(f"{what}: {len(spec_leaves)} specs for "
                             f"{len(leaves)} leaves")
    for leaf, spec in zip(leaves, spec_leaves):
        shards = leaf.addressable_shards
        if {s.device for s in shards} != devices:
            raise AssertionError(f"{what}: a {leaf.shape} leaf lives on "
                                 f"{sorted(s.device.id for s in shards)}")
        want = _expected_shard_shape(leaf.shape, spec or P(), mesh)
        got = {tuple(s.data.shape) for s in shards}
        if got != {want}:
            raise AssertionError(
                f"{what}: {leaf.shape} under {spec} has shards {got}, "
                f"expected {want}"
            )
    return len(leaves)


def _model_axis_allreduce_elements(hlo):
    """Elements moved by all-reduces over the model axis of a (2, 2)
    (data, model) mesh, read off pre-optimization HLO (the replica-group
    patterns are those tests/test_composed.py matches)."""
    import math
    import re

    total = 0
    for ln in hlo.splitlines():
        m = re.search(r"=\s*(.*?)\ball-reduce(-start)?\(", ln)
        if not m or not (
            "replica_groups={{0,1},{2,3}}" in ln
            or re.search(r"replica_groups=\[2,2\]<=\[4\]\b", ln)
        ):
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1)):
            total += math.prod(int(d) for d in dims.split(",") if d)
    return total


def phase_four_chips(lm, cfg, seed, devices):
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd

    steps = cfg["steps"]
    model, params, batch = lm_setup(lm, seed, cfg["global_batch"])
    one = run_plain_lm(
        model, params, batch,
        hvd.build_mesh({"data": 1}, devices=devices[:1]), steps,
    )
    emit("four_chips.reference_one_chip", losses=one["losses"],
         **_timing(one["secs"]))

    mesh4 = hvd.build_mesh({"data": 4}, devices=devices)
    dp = run_plain_lm(model, params, batch, mesh4, steps)
    n = _check_placement(dp["params"], P(), mesh4, "dp4 params")
    n += _check_placement(dp["state"], P(), mesh4, "dp4 optimizer state")
    _check_placement(dp["batch"], P("data"), mesh4, "dp4 batch")
    if "all-reduce" not in dp["text"]:
        raise AssertionError("dp4: no all-reduce in the compiled step")
    emit("four_chips.dp4", losses=dp["losses"], leaves_on_4_devices=n,
         **_timing(dp["secs"]))

    mesh22 = hvd.build_mesh({"data": 2, "model": 2}, devices=devices)
    runs = {"dp4": dp}
    for name, overlap in (("dp2xtp2", False), ("dp2xtp2_fused", True)):
        r = run_composed_lm(lm, params, batch, mesh22, steps,
                            tp_overlap=overlap)
        n = _check_placement(r["params"], r["specs"]["params"], mesh22,
                             f"{name} params")
        n += _check_placement(r["state"], r["specs"]["opt_state"], mesh22,
                              f"{name} optimizer state")
        _check_placement(r["batch"], P("data"), mesh22, f"{name} batch")
        check_kernel_in_program(r["text"], name)
        r["model_elements"] = _model_axis_allreduce_elements(r["hlo"])
        r["permutes"] = r["text"].count("collective-permute")
        emit(f"four_chips.{name}", losses=r["losses"],
             leaves_on_4_devices=n,
             model_axis_allreduce_elements=r["model_elements"],
             collective_permutes=r["permutes"], **_timing(r["secs"]))
        runs[name] = r

    classic, fused = runs["dp2xtp2"], runs["dp2xtp2_fused"]
    # Classic: one [B, T, D] all-reduce per Megatron half-block, forward
    # and backward. Fused: those ride ring permutes instead; what stays on
    # the model axis is the backward of the replicated layer norms ([D]
    # each) and of the token scatter at the embedding (one [B, T, D]).
    tokens_d = (cfg["global_batch"] // 2) * lm["seq"] * lm["d_model"]
    if classic["model_elements"] < 4 * lm["n_layers"] * tokens_d:
        raise AssertionError(
            f"dp2xtp2: {classic['model_elements']} elements all-reduced "
            "over the model axis"
        )
    if not fused["permutes"] or fused["model_elements"] >= 2 * tokens_d:
        raise AssertionError(
            f"dp2xtp2_fused: {fused['permutes']} permutes, "
            f"{fused['model_elements']} elements all-reduced over the "
            "model axis"
        )
    for name, r in runs.items():
        _check_losses(r["losses"], name)
        for a, b in zip(one["losses"], r["losses"]):
            _close(a, b, f"{name} vs one chip")
    emit("four_chips", **cfg, checked=[
        "dp4, dp2xtp2, dp2xtp2_fused losses == one-chip losses",
        "params, optimizer state and batch sharded over all 4 devices "
        "as the rules say",
        "all-reduce in dp4; one model-axis all-reduce per half-block in "
        "dp2xtp2; ring permutes in their place in dp2xtp2_fused",
    ])


# ------------------------------------------------------------------ main


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    started = time.time()

    from horovod_tpu.common.env import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = require_tpu(args.chips)
    before = cache_entries(cache_dir)
    emit("compile_cache", dir=cache_dir, entries_before=before)

    if args.chips == 4:
        phase_four_chips(LM, FOUR, args.seed, devices)
    else:
        params = phase_train_lm(LM, args.seed, devices)
        phase_train_resnet(RESNET, args.seed, devices)
        phase_serve(LM, SERVE, args.seed, params)
        phase_eager(EAGER, started)

    emit("compile_cache", dir=cache_dir, entries_before=before,
         entries_after=cache_entries(cache_dir),
         total_seconds=round(time.time() - started, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
