#!/usr/bin/env python
"""Offline autotuner for the compiled path — emit a pinned ``tuned.json``.

The eager runtime autotunes online (``cpp/src/autotune.cc``); compiled
mode's knobs are trace-time constants, and PRs 7/9 tripled that space:
``HOROVOD_FUSION_THRESHOLD`` x ``HOROVOD_FUSION_FIRST_BUCKET_BYTES``
(together: the ``stream_param_groups`` partition) x topo-plan choice per
collective x ``wire_dtype``. This tool sweeps the joint space with the
GP/EI machinery ported from the native engine (``horovod_tpu/tune/gp.py``
— seeded, byte-deterministic), scoring candidates on two FREE objectives
(no TPU needed):

 - the structural-overlap staircase: independent stream-group count and
   how much backward compute each group's collective can hide behind
   (the pure-python form of ``tools/tpu_profile_overlap.py
   --structural``'s independent-AR-group analysis);
 - the topology compositor's exact alpha-beta pricing
   (``topo.compositor.candidate_plans`` / ``select_plan``) of every
   group's payload under the candidate topo algorithm and wire dtype.

``--measure`` additionally scores each sample by MEASURED step time on
the reachable backend (the free models still run and land in the
evidence block).

The winner is frozen as ``tuned.json``, keyed by an abstract step
signature (param-pytree treedef + leaf shapes/dtypes + mesh axes); it is
consumed by ``make_train_step(tuned=...)`` / ``DistributedOptimizer``
/ ``HOROVOD_TUNED_FILE`` — a signature mismatch there warns loudly and
falls back to untuned defaults. Before pinning, every implied stream-
group plan is checked by the symbolic plan verifier
(``analysis/plan_verify.py``); the tool refuses to emit (exit 5) when a
plan cannot be proven to realize the collective.

Two runs from the same arguments produce BYTE-identical output — the
``make tune-smoke`` CI gate diffs them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _mesh_axes(args) -> dict:
    if args.cross > 1 or args.pod > 1:
        axes = {}
        if args.pod > 1:
            axes["pod"] = int(args.pod)
        axes["cross"] = int(args.cross)
        axes["local"] = int(args.local)
        return axes
    return {"data": int(args.local)}


def _mlp3_params(dim: int):
    """The 3-layer-MLP phase-B program's params avals (the structural
    profiler's program shape, hidden width parameterized)."""
    import jax
    import jax.numpy as jnp

    return {
        f"layer{i}": {
            "w": jax.ShapeDtypeStruct((dim, dim), jnp.float32),
            "b": jax.ShapeDtypeStruct((dim,), jnp.float32),
        }
        for i in range(3)
    }


def _transformer_params(seq_len: int, d_model: int, n_heads: int,
                        n_layers: int, vocab: int):
    """A fp32 TransformerLM program's params avals (dense attention so
    no Pallas trace is needed). The defaults mirror the structural
    profiler's phase-B program; pass your model's dims (e.g. ``--layers
    12 --d-model 768 --vocab 32768 --seq-len 1024``) to emit a tuning
    whose signature matches the step that will load it
    (``make_train_step(..., tuned="tuned.json")``)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import TransformerLM

    def dense_attn(q, k, v):
        B, S, H, D = q.shape
        scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(
            jnp.asarray(D, q.dtype)
        )
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", probs, v)

    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, max_len=seq_len, dtype=jnp.float32,
        attn_fn=dense_attn,
    )
    return jax.eval_shape(
        lambda r, t: model.init(r, t)["params"],
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, seq_len), jnp.int32),
    )


def _build_spec(args, mesh_axes: dict):
    from horovod_tpu import tune as T

    if args.program == "mlp3":
        params = _mlp3_params(args.dim)
    else:
        params = _transformer_params(
            args.seq_len, args.d_model, args.heads, args.layers,
            args.vocab,
        )
    return T.spec_from_params(args.program, params, mesh=mesh_axes), params


def _measure_fn_for(args, params_aval):
    """Concrete-step timer for --measure: builds the real program on the
    reachable backend and times a few steps per candidate config. The
    free objectives still run — this only replaces the score."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu.jax as hvdj
    from horovod_tpu import tune as T
    from horovod_tpu.parallel.mesh import build_mesh

    if args.program != "mlp3":
        raise SystemExit(
            "--measure currently supports --program mlp3 (time the "
            "transformer program through make_train_step(tuned=...))"
        )
    mesh = build_mesh()
    n = len(jax.devices())
    dim = args.dim
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype) + 0.01, params_aval
    )
    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randn(2 * n, dim).astype(np.float32)),
        jnp.asarray(rng.randn(2 * n, dim).astype(np.float32)),
    )

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(3):
            h = jnp.tanh(h @ p[f"layer{i}"]["w"] + p[f"layer{i}"]["b"])
        return jnp.mean((h - y) ** 2)

    tx = optax.sgd(0.01)

    def measure(config) -> float:
        cfg = T.TunedConfig(
            knobs=dict(config), signature={}, objectives={}, baseline={},
        )
        kw = T.tuned_step_kwargs(cfg)
        step = hvdj.make_train_step(
            loss_fn, tx, mesh, donate=False, overlap=True, tuned=False, **kw
        )
        opt_state = tx.init(params)
        p, s, _ = step(params, opt_state, batch)  # compile + warm
        jax.block_until_ready(jax.tree.leaves(p))
        ts = []
        for _ in range(args.measure_reps):
            t0 = time.perf_counter()
            p, s, _ = step(p, s, batch)
            jax.block_until_ready(jax.tree.leaves(p))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    return measure


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Offline GP/EI tuner for the compiled path "
                    "(docs/autotune.md 'Compiled-path offline tuning')"
    )
    ap.add_argument("--program", default="mlp3",
                    choices=["mlp3", "transformer"],
                    help="program to tune: the structural profiler's "
                         "3-layer MLP or small-transformer phase-B "
                         "programs")
    ap.add_argument("--dim", type=int, default=512,
                    help="mlp3 hidden width (512 = the structural "
                         "profiler's shape)")
    ap.add_argument("--seq-len", type=int, default=64,
                    help="transformer sequence length")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--samples", type=int, default=16,
                    help="GP/EI sample budget (incl. the default "
                         "baseline and the corner seeds)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="tuned.json")
    ap.add_argument("--local", type=int, default=8,
                    help="interconnect model: ranks on the inner (ICI) "
                         "hop; with --cross 1 this is a flat data mesh")
    ap.add_argument("--cross", type=int, default=1,
                    help="ranks on the DCN hop (>1 = hierarchical)")
    ap.add_argument("--pod", type=int, default=1,
                    help="ranks on the inter-pod hop")
    ap.add_argument("--generation", default="generic",
                    help="TPU generation for the alpha-beta cost table "
                         "(v3/v4/v5e/v5p/v6e/generic)")
    ap.add_argument("--wire", default="auto",
                    choices=["auto", "f32", "int8"],
                    help="restrict the wire-dtype dim: 'f32' pins full "
                         "precision (tuned step stays bitwise-identical "
                         "to untuned), 'auto' searches both")
    ap.add_argument("--measure", action="store_true",
                    help="score samples by measured step time on the "
                         "reachable backend (free objectives still "
                         "recorded)")
    ap.add_argument("--measure-reps", type=int, default=5)
    ap.add_argument("--calibration", default=None,
                    help="price the search with measured per-hop "
                         "constants from a calibration.json fitted by "
                         "tools/fleet_sim.py --calibrate "
                         "(docs/simulation.md); a stale hop-ladder "
                         "signature warns loudly and the search runs "
                         "on generation defaults")
    ap.add_argument("--zero1", action="store_true",
                    help="tune the streamed-ZeRO-1 reduction shape: "
                         "groups priced as per-bucket reduce-scatter + "
                         "parameter all-gather, 'split' dropped from "
                         "the topo choices, RS+AG plans verified "
                         "before pinning (docs/overlap.md)")
    ap.add_argument("--fixed-comm-us", type=float, default=0.0,
                    help="constant per-step communication OUTSIDE the "
                         "DP staircase — the composed DP x TP psum "
                         "term (sim.tp_fixed_comm_us; "
                         "docs/parallelism.md) — priced into every "
                         "objective so the emitted costs stay honest "
                         "for the composed shape")
    args = ap.parse_args()

    # Planning never needs an accelerator, and one process owns the
    # chip: stay off it (eval_shape is abstract, but --measure and flax
    # tracing may touch the default backend).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from horovod_tpu import tune as T
    from horovod_tpu.common.quant import WIRE_INT8
    from horovod_tpu.topo.model import synthetic_model

    model = synthetic_model(
        local=args.local, cross=args.cross, pod=args.pod,
        generation=args.generation,
    )
    mesh_axes = _mesh_axes(args)
    spec, params_aval = _build_spec(args, mesh_axes)
    space = T.space_for_model(model, allow_int8=args.wire != "f32",
                              zero1=args.zero1)
    if args.wire == "int8":
        # Pin the wire dim at int8 by seeding the default there: the
        # space still carries the dim, the default just starts from it.
        space = T.SearchSpace(
            topo_choices=space.topo_choices, allow_int8=True,
        )

    measure_fn = None
    if args.measure:
        measure_fn = _measure_fn_for(args, params_aval)

    try:
        cfg = T.tune(
            spec, model,
            samples=args.samples, seed=args.seed, space=space,
            measure_fn=measure_fn, zero1=args.zero1,
            calibration=args.calibration,
            fixed_comm_us=args.fixed_comm_us,
        )
    except T.TuneVerificationError as e:
        print(f"[autotune] {e}", file=sys.stderr)
        return 5
    if args.wire == "int8" and cfg.knobs.get("wire_dtype") != WIRE_INT8:
        print(
            "[autotune] note: --wire int8 requested but the objective "
            "preferred f32 at this payload; emitting the winner",
            file=sys.stderr,
        )
    T.save_tuned(cfg, args.out)
    print(json.dumps({
        "program": spec.name,
        "zero1": bool(args.zero1),
        "calibration": cfg.search.get("calibration"),
        "out": args.out,
        "signature": cfg.signature_hash,
        "samples": cfg.search["samples"],
        "knobs": cfg.knobs,
        "objectives": {
            k: cfg.objectives[k]
            for k in ("n_groups", "cost_us", "exposed_us", "wire_bytes")
        },
        "baseline": {
            k: cfg.baseline[k]
            for k in ("n_groups", "cost_us", "exposed_us", "wire_bytes")
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
