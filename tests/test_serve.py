"""``hvd.serve()`` — continuous batching over the composed DP x TP fast
path (docs/serving.md): batcher policy units, paged KV-cache pool,
greedy-decode parity of the batched engine against a one-request-at-a-
time reference, selfdrive SLO-trigger units, serving-sim determinism,
serving fault-site validation, and the HOROVOD_SERVE_* knob registry."""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.common import env as hvd_env
from horovod_tpu.fault.plan import FaultAction, FaultPlan
from horovod_tpu.jax import make_decode_step
from horovod_tpu.models.transformer import TransformerLM, tp_apply
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.run.selfdrive import ServeScalePolicy
from horovod_tpu.serve import (
    ContinuousBatcher,
    PagePool,
    PagePoolExhausted,
    ServeEngine,
    make_decode_state,
)
from horovod_tpu.sim import ServeSimConfig, simulate_serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- batcher
class TestContinuousBatcher:
    def test_full_precedes_deadline(self):
        b = ContinuousBatcher(max_batch_size=4, max_wait_us=1000)
        for i in range(4):
            assert b.offer(f"r{i}", now_us=0)
        d = b.poll(0)
        assert d.ready and d.reason == "full"
        assert d.request_ids == ("r0", "r1", "r2", "r3")
        assert b.depth() == 0

    def test_deadline_fires_on_head_wait(self):
        b = ContinuousBatcher(max_batch_size=4, max_wait_us=1000)
        b.offer("a", now_us=0)
        b.offer("b", now_us=900)
        assert not b.poll(500).ready
        assert b.poll(500).reason == "waiting"
        d = b.poll(1000)  # head has waited exactly max_wait_us
        assert d.ready and d.reason == "deadline"
        assert d.request_ids == ("a", "b")

    def test_starvation_freedom_bound(self):
        # Under trickle pressure the head is never stranded: the next
        # dispatch instant is exactly head-admission + max_wait_us, and
        # assembly is strictly oldest-first.
        b = ContinuousBatcher(max_batch_size=8, max_wait_us=2000)
        b.offer("head", now_us=100)
        for i in range(3):
            b.offer(f"late{i}", now_us=100 + 300 * (i + 1))
        assert b.next_deadline_us() == 2100
        assert not b.poll(2099).ready
        d = b.poll(2100)
        assert d.ready and d.request_ids[0] == "head"
        assert d.request_ids == ("head", "late0", "late1", "late2")

    def test_deterministic_assembly_for_fixed_trace(self):
        trace = [("a", 0), ("b", 10), ("c", 20), ("d", 30), ("e", 40)]

        def replay():
            b = ContinuousBatcher(max_batch_size=2, max_wait_us=1000)
            out = []
            for rid, t in trace:
                b.offer(rid, now_us=t)
                d = b.poll(t)
                if d.ready:
                    out.append((d.reason, d.request_ids))
            d = b.poll(5000)
            if d.ready:
                out.append((d.reason, d.request_ids))
            return out

        first, second = replay(), replay()
        assert first == second
        assert first == [("full", ("a", "b")), ("full", ("c", "d")),
                         ("deadline", ("e",))]

    def test_queue_bound_refuses(self):
        b = ContinuousBatcher(max_batch_size=8, max_wait_us=10,
                              queue_bound=2)
        assert b.offer("a", 0) and b.offer("b", 0)
        assert not b.offer("c", 0)  # refused, not queued
        assert b.depth() == 2

    def test_requeue_goes_to_front_and_bypasses_bound(self):
        b = ContinuousBatcher(max_batch_size=8, max_wait_us=0,
                              queue_bound=2)
        b.offer("a", 0)
        b.offer("b", 0)
        b.requeue("survivor", enqueued_us=0)  # over the bound: allowed
        d = b.poll(0)
        assert d.request_ids[0] == "survivor"

    def test_duplicate_offer_raises(self):
        b = ContinuousBatcher()
        b.offer("a", 0)
        with pytest.raises(ValueError, match="already queued"):
            b.offer("a", 1)

    def test_max_size_caps_batch(self):
        b = ContinuousBatcher(max_batch_size=8, max_wait_us=0)
        for i in range(6):
            b.offer(i, 0)
        d = b.poll(100, max_size=2)  # KV-page pressure
        assert d.ready and d.request_ids == (0, 1)
        assert b.depth() == 4

    def test_from_env(self):
        b = ContinuousBatcher.from_env({
            hvd_env.HOROVOD_SERVE_MAX_BATCH: "3",
            hvd_env.HOROVOD_SERVE_MAX_WAIT_US: "77",
            hvd_env.HOROVOD_SERVE_QUEUE_BOUND: "5",
        })
        assert (b.max_batch_size, b.max_wait_us, b.queue_bound) == (3, 77, 5)


# -------------------------------------------------------------- KV pages
class TestPagePool:
    def test_alloc_is_deterministic_and_skips_scratch(self):
        pool = PagePool(num_pages=8, page_size=4)
        assert pool.pages_free == 7  # page 0 is the scratch page
        pages = pool.alloc(tokens=9)   # ceil(9/4) = 3 pages
        assert pages == [1, 2, 3]
        assert pool.pages_in_use == 3
        assert PagePool.SCRATCH_PAGE not in pages

    def test_alloc_all_or_nothing(self):
        pool = PagePool(num_pages=4, page_size=4)  # 3 usable pages
        with pytest.raises(PagePoolExhausted):
            pool.alloc(tokens=16)  # needs 4
        assert pool.pages_free == 3  # refusal left the pool untouched
        assert pool.can_admit(12) and not pool.can_admit(13)

    def test_free_and_double_free(self):
        pool = PagePool(num_pages=4, page_size=4)
        pages = pool.alloc(tokens=8)
        pool.free(pages)
        assert pool.pages_free == 3
        with pytest.raises(ValueError):
            pool.free(pages)  # double free is a bug, not a no-op
        with pytest.raises(ValueError):
            pool.free([0])    # scratch page is never owned

    def test_freed_pages_are_reused_deterministically(self):
        def replay():
            pool = PagePool(num_pages=8, page_size=4)
            a = pool.alloc(tokens=8)
            pool.free(a)
            b = pool.alloc(tokens=8)
            return a, b

        first, second = replay(), replay()
        assert first == second  # identical sequence -> identical pages
        assert sorted(first[0]) == sorted(first[1])  # same pages reused

    def test_needs_two_pages_minimum(self):
        with pytest.raises(ValueError):
            PagePool(num_pages=1, page_size=4)

    def test_decode_state_geometry(self):
        cache = make_decode_state(2, num_pages=4, page_size=8,
                                  n_heads=2, head_dim=4)
        assert sorted(cache) == ["block_0", "block_1"]
        k = cache["block_0"]["attention"]["cache_k"]
        assert k.shape == (4, 8, 2, 4)
        assert k.dtype == jnp.bfloat16  # serving default


# ------------------------------------------------------- decode parity
VOCAB, D, HEADS, LAYERS, T = 32, 16, 2, 1, 32


def _tiny_params():
    model = TransformerLM(vocab_size=VOCAB, d_model=D, n_heads=HEADS,
                          n_layers=LAYERS, max_len=T)
    return model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)
    )["params"]


def _prompts(n=6, seed=0):
    rng = np.random.RandomState(seed)
    return [
        [int(t) for t in rng.randint(0, VOCAB, size=rng.randint(1, 6))]
        for _ in range(n)
    ]


def _reference_greedy(params, prompt, max_tokens):
    """One-request-at-a-time full-recompute greedy decode via the dense
    ``tp_apply`` reference — no KV cache, no batching."""
    seq = list(prompt)
    for _ in range(max_tokens):
        logits = tp_apply(
            params, jnp.asarray([seq], jnp.int32), n_heads=HEADS,
            model_axis=None, dtype=jnp.float32,
        )
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[len(prompt):]


def _run_engine(params, step, prompts, max_tokens=4, replicas=1):
    engine = ServeEngine(
        params, step,
        n_layers=LAYERS, n_heads=HEADS, head_dim=D // HEADS,
        num_pages=64, page_size=4, max_batch_size=4, max_wait_us=500,
        max_context=T, replicas=replicas, cache_dtype=jnp.float32,
    )
    with engine:
        rids = [engine.submit(p, max_tokens=max_tokens) for p in prompts]
        engine.drain(timeout=120.0)
    return [engine.result(r) for r in rids]


def test_batched_engine_matches_one_at_a_time_reference():
    params = _tiny_params()
    step = make_decode_step(n_heads=HEADS, dtype=jnp.float32)
    prompts = _prompts()
    got = _run_engine(params, step, prompts)
    for prompt, completion in zip(prompts, got):
        assert completion.outcome == "ok"
        assert list(completion.tokens) == \
            _reference_greedy(params, prompt, 4), (
                f"paged batched decode diverged for prompt {prompt}"
            )


def _one_batch_engine(params, requests):
    """All ``requests`` (prompt, max_tokens) submitted BEFORE the engine
    starts, so the first dequeue takes them as one batch."""
    engine = ServeEngine(
        params, make_decode_step(n_heads=HEADS, dtype=jnp.float32),
        n_layers=LAYERS, n_heads=HEADS, head_dim=D // HEADS,
        num_pages=64, page_size=4, max_batch_size=4, max_wait_us=500,
        max_context=T, cache_dtype=jnp.float32,
    )
    rids = [engine.submit(p, max_tokens=n) for p, n in requests]
    with engine:
        engine.drain(timeout=120.0)
    return engine, [engine.result(r) for r in rids]


def test_completion_carries_one_time_per_output_token():
    requests = [([1, 2, 3], 5), ([4], 2), ([5, 6], 4)]
    _, got = _one_batch_engine(_tiny_params(), requests)
    for (_, n), c in zip(requests, got):
        assert c.outcome == "ok" and len(c.tokens) == n
        assert len(c.token_times_s) == n
        # one stamp per decode launch, one token a launch: strictly rising,
        # from submit, the last no later than the request's own latency
        assert all(b > a for a, b in zip(c.token_times_s, c.token_times_s[1:]))
        assert 0.0 < c.token_times_s[0]
        assert c.token_times_s[-1] <= c.latency_s + 0.05
    # refused requests carry no token times
    engine = ServeEngine(
        _tiny_params(), make_decode_step(n_heads=HEADS, dtype=jnp.float32),
        n_layers=LAYERS, n_heads=HEADS, head_dim=D // HEADS, num_pages=8,
        page_size=4, max_batch_size=2, max_context=T, queue_bound=1,
    )
    engine.submit([1], max_tokens=1)
    refused = engine.result(engine.submit([1], max_tokens=1))
    assert refused.outcome == "rejected" and refused.token_times_s == ()


def test_launch_counters_add_up_on_members_of_different_lengths():
    # a member needs len(prompt) + max_tokens - 1 launches: 7, 2 and 5
    requests = [([1, 2, 3], 5), ([4], 2), ([5, 6], 4)]
    engine, got = _one_batch_engine(_tiny_params(), requests)
    assert [c.outcome for c in got] == ["ok"] * 3
    assert engine.batches == 1 and engine.batched_requests == 3
    assert engine.launches == 7            # the batch runs to its longest
    assert engine.slot_launches == 7 + 2 + 5
    occupancy = engine.slot_launches / (engine.launches * 4)
    assert occupancy == pytest.approx(0.5)


def test_tp_sharded_decode_matches_dense(devices):
    params = _tiny_params()
    mesh = build_mesh({"model": 2}, devices=devices[:2])
    dense = make_decode_step(n_heads=HEADS, dtype=jnp.float32)
    tp = make_decode_step(n_heads=HEADS, mesh=mesh, rules="gpt",
                          dtype=jnp.float32)
    prompts = _prompts(n=4, seed=3)
    a = _run_engine(params, dense, prompts)
    b = _run_engine(params, tp, prompts)
    assert [list(c.tokens) for c in a] == [list(c.tokens) for c in b]


def test_make_decode_step_validates_mesh_rules_pairing(devices):
    mesh = build_mesh({"model": 2}, devices=devices[:2])
    with pytest.raises(ValueError, match="rules"):
        make_decode_step(n_heads=HEADS, mesh=mesh)  # mesh without rules
    with pytest.raises(ValueError, match="mesh"):
        make_decode_step(n_heads=HEADS, rules="gpt")  # rules without mesh
    with pytest.raises(ValueError, match="needs axis 'tensor'"):
        make_decode_step(n_heads=HEADS, mesh=mesh, rules="gpt",
                         model_axis="tensor")


def test_engine_refuses_oversized_and_duplicate_requests():
    params = _tiny_params()
    step = make_decode_step(n_heads=HEADS, dtype=jnp.float32)
    engine = ServeEngine(
        params, step,
        n_layers=LAYERS, n_heads=HEADS, head_dim=D // HEADS,
        num_pages=8, page_size=4, max_context=T,
        cache_dtype=jnp.float32,
    )
    with engine:
        with pytest.raises(ValueError):
            engine.submit([], max_tokens=4)  # empty prompt
        with pytest.raises(ValueError):
            engine.submit([1, 2], max_tokens=T)  # prompt+tokens > context
        engine.submit([1, 2], max_tokens=1, request_id="dup")
        with pytest.raises(ValueError):
            engine.submit([3], max_tokens=1, request_id="dup")
        engine.drain(timeout=60.0)


# -------------------------------------------------- selfdrive SLO hook
class TestServeScalePolicy:
    @staticmethod
    def _fill(policy, depth=0.0, viol=0, done=0, beats=None):
        for _ in range(policy.window if beats is None else beats):
            policy.observe(depth, viol, done)

    def test_cold_start_returns_none(self):
        p = ServeScalePolicy(window=4, cooldown=0)
        self._fill(p, depth=100.0, viol=10, done=10, beats=3)
        assert p.decide(1) is None  # window not yet filled

    def test_scale_out_on_queue_depth(self):
        p = ServeScalePolicy(scale_out_depth=16.0, window=2, cooldown=0)
        self._fill(p, depth=20.0, done=5)
        d = p.decide(1)
        assert d is not None and d.action == "scale-out"
        assert d.reason == "queue-depth"

    def test_scale_out_on_slo_burn(self):
        p = ServeScalePolicy(scale_out_depth=100.0, slo_burn=0.1,
                             window=2, cooldown=0)
        self._fill(p, depth=1.0, viol=3, done=10)  # 30% burn
        d = p.decide(1)
        assert d is not None and d.action == "scale-out"
        assert d.reason == "slo-burn"
        assert d.slo_burn == pytest.approx(0.3)

    def test_max_replicas_veto(self):
        p = ServeScalePolicy(scale_out_depth=1.0, window=1, cooldown=0,
                             max_replicas=2)
        self._fill(p, depth=50.0, done=5)
        assert p.decide(2) is None

    def test_scale_in_when_idle_and_min_veto(self):
        p = ServeScalePolicy(scale_in_depth=1.0, window=2, cooldown=0,
                             min_replicas=1)
        self._fill(p, depth=0.0, done=4)
        d = p.decide(2)
        assert d is not None and d.action == "scale-in"
        assert d.reason == "idle"
        p2 = ServeScalePolicy(scale_in_depth=1.0, window=2, cooldown=0)
        self._fill(p2, depth=0.0, done=4)
        assert p2.decide(1) is None  # already at min_replicas

    def test_idle_fleet_is_not_burning(self):
        p = ServeScalePolicy(window=2, cooldown=0)
        self._fill(p, depth=0.0, viol=0, done=0)
        assert p.burn() == 0.0
        assert p.decide(1) is None

    def test_cooldown_blocks_thrash(self):
        p = ServeScalePolicy(scale_out_depth=4.0, window=1, cooldown=2)
        p.observe(10.0, 0, 5)
        assert p.decide(1) is not None
        for _ in range(2):
            p.observe(10.0, 0, 5)
            assert p.decide(1) is None  # inside the cooldown
        p.observe(10.0, 0, 5)
        assert p.decide(1) is not None  # cooldown expired

    def test_from_env(self):
        p = ServeScalePolicy.from_env({
            hvd_env.HOROVOD_SERVE_SCALE_OUT_DEPTH: "9.5",
            hvd_env.HOROVOD_SERVE_SCALE_IN_DEPTH: "0.5",
            hvd_env.HOROVOD_SERVE_SLO_BURN: "0.25",
            hvd_env.HOROVOD_SERVE_SCALE_WINDOW: "3",
            hvd_env.HOROVOD_SERVE_SCALE_COOLDOWN: "1",
        }, min_replicas=2, max_replicas=4)
        assert p.scale_out_depth == 9.5
        assert p.scale_in_depth == 0.5
        assert p.slo_burn == 0.25
        assert (p.window, p.cooldown) == (3, 1)
        assert (p.min_replicas, p.max_replicas) == (2, 4)


# ----------------------------------------------------------- fleet sim
class TestServeSim:
    def test_report_is_deterministic(self):
        cfg = ServeSimConfig(qps=200.0, duration_s=2.0, seed=11)
        a = json.dumps(simulate_serve(cfg), sort_keys=True)
        b = json.dumps(simulate_serve(cfg), sort_keys=True)
        assert a == b

    def test_p99_rises_with_offered_load(self):
        p99 = [
            simulate_serve(
                ServeSimConfig(qps=q, duration_s=2.0, seed=0)
            )["latency_ms"]["p99"]
            for q in (50.0, 400.0, 1600.0)
        ]
        assert p99 == sorted(p99), f"p99 not monotone in qps: {p99}"
        assert p99[0] < p99[-1]

    def test_arrival_seed_changes_trace(self):
        base = ServeSimConfig(qps=200.0, duration_s=2.0, seed=0)
        other = ServeSimConfig(qps=200.0, duration_s=2.0, seed=1)
        assert simulate_serve(base) != simulate_serve(other)

    def test_faults_honored_and_exactly_once(self):
        plan = FaultPlan.from_json(json.dumps({
            "seed": 5,
            "faults": [
                {"kind": "drop", "site": "request", "after": 10,
                 "count": 30},
                {"kind": "kill_replica", "at_step": 3},
            ],
        }))
        cfg = ServeSimConfig(qps=200.0, duration_s=2.0, replicas=2,
                             seed=5)
        rep = simulate_serve(cfg, fault_plan=plan)
        assert rep["dropped"] > 0
        assert rep["replicas_killed"] == 1
        assert rep["requeued"] > 0
        assert rep["unanswered"] == 0  # every admitted request answered
        assert rep["arrivals"] == (
            rep["served"] + rep["dropped"] + rep["rejected"]
        )

    def test_queue_bound_rejects_under_overload(self):
        cfg = ServeSimConfig(qps=4000.0, duration_s=1.0, replicas=1,
                             queue_bound=8, seed=2)
        rep = simulate_serve(cfg)
        assert rep["rejected"] > 0
        assert rep["unanswered"] == 0


# ------------------------------------------------- fault site contract
class TestServingFaultSites:
    def test_kill_replica_defaults_to_replica_site(self):
        a = FaultAction.from_dict(
            {"kind": "kill_replica", "at_step": 1}, 0
        )
        assert a.site == "replica"

    def test_kind_site_mismatches_rejected(self):
        with pytest.raises(ValueError):
            FaultAction.from_dict(
                {"kind": "kill_replica", "site": "request", "at_step": 1},
                0,
            )
        with pytest.raises(ValueError):
            FaultAction.from_dict(
                {"kind": "drop", "site": "replica", "at_step": 1}, 0
            )
        with pytest.raises(ValueError):
            FaultAction.from_dict(
                {"kind": "kill", "site": "request", "at_step": 1}, 0
            )

    def test_request_site_carries_drop_and_delay(self):
        plan = FaultPlan.from_json(json.dumps({
            "seed": 0,
            "faults": [
                {"kind": "drop", "site": "request", "at_step": 1},
                {"kind": "delay", "site": "request", "at_step": 2,
                 "ms": 5},
            ],
        }))
        kinds = {a.kind for a in plan.actions}
        assert kinds == {"drop", "delay"}


# ---------------------------------------------------- knob registry
def _serve_knobs_in_sources():
    """Every HOROVOD_SERVE_* token referenced anywhere in the package."""
    found = set()
    for root, _dirs, files in os.walk(os.path.join(REPO, "horovod_tpu")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                found.update(re.findall(r"HOROVOD_SERVE_[A-Z_]+", f.read()))
    return found


def test_every_serve_knob_is_declared_in_env():
    knobs = _serve_knobs_in_sources()
    assert knobs, "no HOROVOD_SERVE_* knobs found (scan broken?)"
    for knob in sorted(knobs):
        assert getattr(hvd_env, knob, None) == knob, (
            f"{knob} is referenced in sources but not declared in "
            f"common/env.py — unknown serving knobs are a bug"
        )


def test_config_from_env_parses_serve_knobs(monkeypatch):
    values = {
        hvd_env.HOROVOD_SERVE: "1",
        hvd_env.HOROVOD_SERVE_PORT: "8123",
        hvd_env.HOROVOD_SERVE_REPLICAS: "3",
        hvd_env.HOROVOD_SERVE_MAX_BATCH: "16",
        hvd_env.HOROVOD_SERVE_MAX_WAIT_US: "777",
        hvd_env.HOROVOD_SERVE_QUEUE_BOUND: "9",
        hvd_env.HOROVOD_SERVE_SLO_MS: "42.5",
        hvd_env.HOROVOD_SERVE_MAX_TOKENS: "5",
        hvd_env.HOROVOD_SERVE_KV_PAGES: "33",
        hvd_env.HOROVOD_SERVE_PAGE_SIZE: "8",
    }
    for k, v in values.items():
        monkeypatch.setenv(k, v)
    cfg = hvd_env.Config.from_env()
    assert cfg.serve is True
    assert cfg.serve_port == 8123
    assert cfg.serve_replicas == 3
    assert cfg.serve_max_batch == 16
    assert cfg.serve_max_wait_us == 777
    assert cfg.serve_queue_bound == 9
    assert cfg.serve_slo_ms == 42.5
    assert cfg.serve_max_tokens == 5
    assert cfg.serve_kv_pages == 33
    assert cfg.serve_page_size == 8
