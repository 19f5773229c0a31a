"""Convergence evidence for the lossy/sharded data paths.

Trains the same small transformer LM from identical init on identical
batches under three gradient paths — full-precision DP, int8-quantized
wire (``ops/quantized.py``), and int8 wire composed with ZeRO-1 sharded
optimizer state (``parallel/zero.py``) — and records the loss curves.
This backs the "~1% gradient noise is acceptable" claim with an actual
end-to-end trajectory instead of per-call error bounds: the quantized curves must track fp32 within a small relative
gap, not merely bound per-step error.

Run standalone for the committed artifact (8 virtual CPU devices):

    python -m horovod_tpu.utils.convergence --steps 300

prints one JSON line with the curves and final-loss gaps; the test suite
runs fewer steps and asserts the gap bound.
"""

from __future__ import annotations

import argparse
import json
import sys


def run(steps: int = 300, record_every: int = 10, seed: int = 0,
        d_model: int = 128, n_layers: int = 2, n_heads: int = 4,
        vocab: int = 512, seq_len: int = 64, batch_per_dev: int = 2,
        lr: float = 1e-3, n_batches: int = 8) -> dict:
    """Returns {"curves": {cfg: [loss...]}, "final": {...},
    "rel_gap_vs_fp32": {...}}; same init, same data order per config."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvdj
    from horovod_tpu.jax import _shard_map
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.mesh import build_mesh
    from horovod_tpu.parallel.zero import init_zero1_state, zero1_update

    devices = jax.devices()
    n_dev = len(devices)
    mesh = build_mesh({"data": n_dev})
    global_batch = batch_per_dev * n_dev

    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, max_len=seq_len,
    )
    rng = np.random.RandomState(seed)
    # A small fixed dataset the model can start memorizing within a few
    # hundred steps — the curves must move, or the comparison is vacuous.
    data = [
        (jnp.asarray(rng.randint(0, vocab, (global_batch, seq_len)),
                     jnp.int32),
         jnp.asarray(rng.randint(0, vocab, (global_batch, seq_len)),
                     jnp.int32))
        for _ in range(n_batches)
    ]
    params0 = model.init(jax.random.PRNGKey(seed), data[0][0][:1])["params"]
    tx = optax.adamw(lr)

    def loss_fn(p, tok, lab):
        logits = model.apply({"params": p}, tok)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, lab
        ).mean()

    def make_replicated_step(quantized):
        def step(p, s, tok, lab):
            loss, grads = jax.value_and_grad(loss_fn)(p, tok, lab)
            grads = hvdj.allreduce_gradients(grads, quantized=quantized)
            updates, s = tx.update(grads, s, p)
            p = optax.apply_updates(p, updates)
            return p, s, jax.lax.pmean(loss, "data")

        return jax.jit(_shard_map(
            step, mesh,
            in_specs=(P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P()),
        ))

    def make_zero1_step(quantized):
        def step(p, s_stacked, tok, lab):
            s = jax.tree.map(lambda x: x[0], s_stacked)
            loss, grads = jax.value_and_grad(loss_fn)(p, tok, lab)
            p, s = zero1_update(
                tx, p, s, grads, axis_name="data", n_shards=n_dev,
                quantized=quantized,
            )
            return (p, jax.tree.map(lambda x: x[None], s),
                    jax.lax.pmean(loss, "data"))

        return jax.jit(_shard_map(
            step, mesh,
            in_specs=(P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P("data"), P()),
        ))

    configs = {
        "fp32": (make_replicated_step(False), lambda: tx.init(params0)),
        "quantized": (make_replicated_step(True), lambda: tx.init(params0)),
        "quantized+zero1": (
            make_zero1_step(True),
            lambda: init_zero1_state(tx, params0, n_dev, quantized=True),
        ),
    }

    curves: dict = {}
    for name, (step_fn, init_state) in configs.items():
        p = jax.tree.map(jnp.copy, params0)
        s = init_state()
        losses = []
        for i in range(steps):
            tok, lab = data[i % n_batches]
            p, s, loss = step_fn(p, s, tok, lab)
            if i % record_every == 0 or i == steps - 1:
                losses.append(round(float(loss), 4))
        curves[name] = losses

    final = {k: v[-1] for k, v in curves.items()}
    gaps = {
        k: round(abs(v - final["fp32"]) / max(final["fp32"], 1e-9), 4)
        for k, v in final.items()
    }
    return {
        "n_devices": n_dev,
        "steps": steps,
        "model": {
            "d_model": d_model, "n_layers": n_layers, "vocab": vocab,
            "seq_len": seq_len, "global_batch": global_batch,
            "optimizer": f"adamw(lr={lr})",
        },
        "curves": curves,
        "final_loss": final,
        "rel_gap_vs_fp32": gaps,
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--cpu-devices", type=int, default=8)
    args = parser.parse_args()

    import os
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    new = f"--xla_force_host_platform_device_count={args.cpu_devices}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", new, flags
        )
    else:
        flags = (flags + " " + new).strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

    print(json.dumps(run(steps=args.steps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
