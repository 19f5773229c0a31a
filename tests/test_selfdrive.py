"""Self-driving fleet (ISSUE 14, docs/fault_tolerance.md "Self-driving
fleet"): the StragglerPolicy decision ladder, the live re-plan proposal/
verification/adoption chain, the hot-spare helpers, the chronic-slowness
fault shape, the journal v2 schema, and the skew-tracker generation
re-keying — plus the seeded quarantine→re-plan→promote→recover e2e whose
normalized event log must be byte-identical across runs (the heavy e2e
is ``slow``-marked; ``make selfdrive-smoke`` runs it twice in CI)."""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from horovod_tpu.run import selfdrive as sd  # noqa: E402
from horovod_tpu.run.journal import DriverJournal  # noqa: E402
from horovod_tpu.topo.model import synthetic_model  # noqa: E402
from horovod_tpu.tune.objective import ProgramSpec, calibrated_model  # noqa: E402
from horovod_tpu.sim.calibrate import (  # noqa: E402
    Calibration,
    model_signature,
    save_calibration,
)


# ------------------------------------------------------ StragglerPolicy
def _charged(policy, steps, rank):
    for s in steps:
        policy.observe(s, 0.2, rank, True)


def test_policy_disabled_by_default():
    pol = sd.StragglerPolicy.from_env({})
    assert not pol.enabled
    _charged(pol, range(10), 1)
    assert pol.decide({0: "a", 1: "b"}, {"a": 1, "b": 1}, 1) is None


def test_policy_strike_accumulation_2_ranks():
    pol = sd.StragglerPolicy(strikes=3, window=6)
    _charged(pol, [0, 1], 1)
    assert pol.decide({0: "a", 1: "b"}, {"a": 2, "b": 2}, 2) is None
    _charged(pol, [2], 1)
    d = pol.decide({0: "a", 1: "b"}, {"a": 2, "b": 2}, 2)
    assert d is not None and d.host == "b" and d.rank == 1
    assert d.charges == 3 and d.window == 6


def test_policy_decay_healthy_steps_push_charges_out():
    """A rank that recovers decays out: the window is the last N STEPS,
    not the last N charges."""
    pol = sd.StragglerPolicy(strikes=3, window=4)
    _charged(pol, [0, 1], 1)
    # Three healthy steps (below threshold: charged=False) slide two of
    # the charges out of the 4-step window.
    for s in (2, 3, 4):
        pol.observe(s, 0.001, 0, False)
    assert pol.charges().get(1, 0) == 1
    assert pol.decide({0: "a", 1: "b"}, {"a": 2, "b": 2}, 2) is None


def test_policy_never_quarantines_below_min_world():
    pol = sd.StragglerPolicy(strikes=2, window=4)
    _charged(pol, [0, 1, 2], 1)
    # Removing host b leaves 1 < min_world=2: vetoed, and the veto is
    # counted (the driver logs it).
    assert pol.decide({0: "a", 1: "b"}, {"a": 1, "b": 1}, 2) is None
    assert pol.vetoes == 1
    # With spare capacity on a healthy host the same evidence decides.
    d = pol.decide({0: "a", 1: "b"}, {"a": 2, "b": 1}, 2)
    assert d is not None and d.host == "b"


def test_policy_one_host_per_beat_4_ranks():
    """Two hosts over threshold in the same window: one decision per
    call (one per supervision beat), most-charged first, and the
    decided rank's evidence is consumed."""
    pol = sd.StragglerPolicy(strikes=2, window=8)
    r2h = {0: "a", 1: "a", 2: "b", 3: "b"}
    caps = {"a": 2, "b": 2, "c": 2}
    _charged(pol, [0, 1, 2], 3)   # rank 3 (host b): 3 charges
    _charged(pol, [3, 4], 1)      # rank 1 (host a): 2 charges
    d1 = pol.decide(r2h, caps, 2)
    assert d1 is not None and (d1.host, d1.rank) == ("b", 3)
    # Same beat cannot fell a second host; the NEXT beat may.
    d2 = pol.decide(r2h, caps, 2)
    assert d2 is not None and (d2.host, d2.rank) == ("a", 1)
    assert pol.decide(r2h, caps, 2) is None  # all evidence spent


def test_policy_relapse_ledgers_are_independent():
    """Slow-quarantine relapse doubling rides its own strike ledger —
    death strikes never compound a slowness sentence (and vice versa)."""
    from horovod_tpu.run.elastic_driver import ElasticDriver

    drv = ElasticDriver.__new__(ElasticDriver)  # unit scope
    drv._blacklist = {}
    drv._blacklist_reason = {}
    drv._quarantine_strikes = {"h": 5}  # prior DEATH history
    drv._slow_strikes = {}
    drv._quarantine_cooldown = 10.0
    drv._blacklist_cooldown = 10.0
    drv._output_dir = None
    decision = sd.QuarantineDecision(host="h", rank=1, charges=3, window=6)
    drv._quarantine_slow_host(decision)
    assert drv._slow_strikes["h"] == 1
    assert drv._blacklist_reason["h"] == "slow"
    first_deadline = drv._blacklist["h"]
    assert first_deadline - time.monotonic() <= 10.0 + 0.5  # NOT 2^5-scaled
    # Relapse: the second slowness quarantine doubles.
    del drv._blacklist["h"]
    drv._quarantine_slow_host(decision)
    assert drv._slow_strikes["h"] == 2
    assert drv._blacklist["h"] - time.monotonic() > 15.0
    # Death history untouched by the slow ledger.
    assert drv._quarantine_strikes["h"] == 5


def test_policy_reset_on_generation_change():
    pol = sd.StragglerPolicy(strikes=2, window=8)
    _charged(pol, [0, 1, 2], 1)
    pol.reset_generation(2)
    assert pol.charges() == {}
    assert pol.generation == 2
    assert pol.decide({0: "a", 1: "b"}, {"a": 2, "b": 2}, 1) is None


def test_driver_quarantine_respects_available_capacity():
    """_maybe_quarantine_slow end to end on a bare driver: vetoed when
    the remaining capacity is short, fires when a spare-capable host
    covers min-np, and re-forms without the offender."""
    from horovod_tpu.run.elastic_driver import ElasticDriver

    def bare(hosts):
        drv = ElasticDriver.__new__(ElasticDriver)
        drv._policy = sd.StragglerPolicy(strikes=2, window=4)
        drv._adopting = False
        drv._min_np = 2
        drv._static_hosts = hosts
        drv._script = None
        drv._last_hosts = []
        drv._blacklist = {}
        drv._blacklist_reason = {}
        drv._quarantine_strikes = {}
        drv._slow_strikes = {}
        drv._failures = {}
        drv._last_failure = {}
        drv._quarantine_cooldown = 60.0
        drv._blacklist_cooldown = 60.0
        drv._output_dir = None
        drv._last_world = {
            "assignments": {
                "hostA:0": {"rank": 0},
                "hostB:0": {"rank": 1},
            }
        }
        _charged(drv._policy, [0, 1], 1)  # rank 1 = hostB is the sloth
        return drv

    tight = bare([("hostA", 1), ("hostB", 1)])
    assert tight._maybe_quarantine_slow() is False
    assert tight._blacklist == {}

    roomy = bare([("hostA", 2), ("hostB", 1)])
    assert roomy._maybe_quarantine_slow() is True
    assert roomy._blacklist_reason["hostB"] == "slow"
    assert "hostB" not in dict(roomy._discover())


# --------------------------------------------------- skew tracker re-key
def _win(rank, steps, gen=None):
    doc = {"steps": [[i, float(i), float(i) + 0.1 * (rank + 1)]
                     for i in steps]}
    if gen is not None:
        doc["gen"] = gen
    return {rank: doc}


def test_skew_tracker_generation_gate_and_reset():
    """Satellite regression: after a generation bump, cumulative windows
    from the old world must never charge the new world's (renumbered)
    ranks — and a parked/removed rank is never charged at all."""
    from horovod_tpu.trace.pusher import StepSkewTracker

    sk = StepSkewTracker(threshold_s=0.05)
    sk.reset_generation(1)
    w = {**_win(0, [0, 1], gen=1), **_win(1, [0, 1], gen=1)}
    out = sk.update(w)
    assert [t[0] for t in out] == [0, 1]
    assert all(worst == 1 for _, _, worst in out)  # rank 1 ends later
    # Generation bump: rank 1's old window lingers on the KV plane while
    # the new gen-2 world (where "rank 1" is a different process) starts
    # its ledger from 0. Without the re-key these step indices would
    # collide and charge the wrong rank.
    sk.reset_generation(2)
    stale = {**_win(1, [2, 3], gen=1)}          # departed rank, old gen
    fresh = {**_win(0, [0, 1], gen=2), **_win(1, [0], gen=2)}
    assert sk.update(stale) == []               # never charged
    out = sk.update({**stale, **fresh})
    assert [t[0] for t in out] == [0]           # only the common fresh step
    # And the old generation's charged indices did not leak: step 0/1
    # were re-emitted for gen 2 even though gen 1 already charged them.
    assert len(out) == 1


def test_trace_tap_reset_steps_restarts_ledger():
    from horovod_tpu import trace as tr

    tap = tr.TraceTap(ring_capacity=64)
    tok = tap.begin_step()
    tap.end_step(tok)
    assert tap.window()["steps"]
    tap.reset_steps()
    w = tap.window()
    assert w["steps"] == []
    tok = tap.begin_step()
    assert tok[0] == 0  # indices restart for the new generation


def test_trace_window_carries_generation(monkeypatch):
    from horovod_tpu import trace as tr

    tap = tr.TraceTap(ring_capacity=16)
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "7")
    assert tap.window()["gen"] == 7
    monkeypatch.delenv("HOROVOD_ELASTIC_GEN")
    assert tap.window()["gen"] == 0


# ------------------------------------------------- chronic delay shape
def test_fault_plan_every_until_window_and_validation():
    from horovod_tpu.fault.plan import FaultPlan

    plan = FaultPlan.from_json(json.dumps({
        "seed": 9, "faults": [
            {"kind": "delay", "rank": 0, "site": "step",
             "seconds": 0.1, "after": 2, "every": 3, "until": 11},
        ],
    }))
    a = plan.actions[0]
    assert [h for h in range(1, 15) if a.in_window(h)] == [3, 6, 9]
    # Round-trips through the canonical schedule.
    sched = json.loads(plan.canonical_schedule())
    assert sched["schedule"][0]["every"] == 3
    assert sched["schedule"][0]["until"] == 11

    def bad(fault):
        with pytest.raises(ValueError):
            FaultPlan.from_json(json.dumps({"seed": 0, "faults": [fault]}))

    bad({"kind": "kill", "every": 2})              # delay-only shape
    bad({"kind": "drop", "site": "rpc", "until": 5})
    bad({"kind": "delay", "every": 0})             # period must be >= 1
    bad({"kind": "delay", "after": 5, "until": 5})  # empty window


def test_fault_plan_every_stream_purity():
    """The probabilistic stream advances only on firing hits, so the
    chronic form's schedule is a pure function of (seed, action, rank)."""
    from horovod_tpu.fault.plan import FaultPlan

    text = json.dumps({
        "seed": 31, "faults": [
            {"kind": "delay", "rank": 1, "site": "step", "seconds": 0.01,
             "after": 0, "every": 2, "until": 40, "frac": 0.5},
        ],
    })
    s1 = FaultPlan.from_json(text).canonical_schedule()
    s2 = FaultPlan.from_json(text).canonical_schedule()
    assert s1 == s2


def test_sim_honors_recurring_delay():
    """sim/core.py draws the chronic shape: a delay with every=2 over
    steps 1..6 stretches EXACTLY the faulted rank's steps 0, 2 and 4 (0-
    indexed) by exactly the injected microseconds."""
    from horovod_tpu.fault.plan import FaultPlan
    from horovod_tpu.sim.core import program_from_layers, simulate

    model = synthetic_model(4)
    program = program_from_layers("t", [1 << 20] * 4)
    plan = FaultPlan.from_json(json.dumps({
        "seed": 5, "faults": [
            {"kind": "delay", "rank": 1, "site": "step",
             "seconds": 0.002, "after": 0, "every": 2, "until": 6},
        ],
    }))
    res = simulate(model, program, steps=6, fault_plan=plan)
    hits = [(s, d) for s, _, d in res.fault_instants.get(1, [])]
    assert hits == [(0, 2000.0), (2, 2000.0), (4, 2000.0)]
    base = simulate(model, program, steps=6)
    # Only the faulted steps stretched, and by exactly the delay (the
    # fleet is synchronous at these payloads).
    diffs = [
        round(a - b, 4) for a, b in
        zip(res.step_times_us, base.step_times_us)
    ]
    assert diffs == [2000.0, 0.0, 2000.0, 0.0, 2000.0, 0.0]


# ------------------------------------------------------- journal v2
def test_journal_v2_roundtrip_with_selfdrive_records(tmp_path):
    p = str(tmp_path / "driver_journal.json")
    j = DriverJournal.open(p)
    j.record(
        gen=3,
        slow_strikes={"hostA": 2},
        blacklist_reasons={"hostA": "slow"},
        replan={"id": 1, "gen": 3, "config": {"wire_dtype": "int8"}},
        spare_ids=["hostB:1"],
    )
    j2 = DriverJournal.open(p)
    st = j2.state
    assert st["slow_strikes"] == {"hostA": 2}
    assert st["blacklist_reasons"] == {"hostA": "slow"}
    assert st["replan"]["config"]["wire_dtype"] == "int8"
    assert st["spare_ids"] == ["hostB:1"]
    # Replay is still idempotent bytes->state.
    assert DriverJournal(p).replay() == DriverJournal(p).replay()


def test_journal_v1_replays_cleanly(tmp_path):
    """Backward compat: a pre-selfdrive journal (version 1, no v2 keys)
    resumes exactly as before."""
    p = str(tmp_path / "driver_journal.json")
    with open(p, "w") as f:
        json.dump({"version": 1, "epoch": 2, "gen": 4,
                   "blacklist": {}, "strikes": {"h": 1}}, f)
    j = DriverJournal.open(p)
    assert j.epoch == 3  # open bumps
    assert j.state["gen"] == 4
    assert j.state["strikes"] == {"h": 1}


def test_resume_mid_quarantine_replays_the_same_fleet_state(tmp_path):
    """Acceptance (ISSUE 14): a driver resumed from a journal written
    mid-quarantine restores the slowness verdict — the host stays out
    under ``reason="slow"`` with its slow-strike ledger (relapse
    doubling intact) — and the published re-plan notice, epoch-
    refreshed so workers above the old epoch's fence still accept it."""
    from horovod_tpu.run.elastic_driver import ElasticDriver

    td = str(tmp_path)
    j = DriverJournal.open(os.path.join(td, "driver_journal.json"))
    j.record(
        gen=2,
        world={"gen": 2, "epoch": 1, "size": 2, "assignments": {
            "127.0.0.1:0": {"rank": 0, "local_rank": 0, "local_size": 2,
                            "cross_rank": 0, "cross_size": 1},
            "127.0.0.1:1": {"rank": 1, "local_rank": 1, "local_size": 2,
                            "cross_rank": 0, "cross_size": 1},
        }},
        kv_port=0,
        blacklist=__import__(
            "horovod_tpu.run.journal", fromlist=["blacklist_to_journal"]
        ).blacklist_to_journal({"slowhost": time.monotonic() + 120.0}),
        blacklist_reasons={"slowhost": "slow"},
        slow_strikes={"slowhost": 2},
        strikes={"deadhost": 1},
        replan={"id": 3, "gen": 2, "epoch": 1, "calib": "abc",
                "config": {"wire_dtype": "int8"}},
    )
    drv = ElasticDriver(
        ["true"], min_np=2, max_np=2,
        hosts=[("127.0.0.1", 2)], output_dir=td, resume=True,
    )
    try:
        assert drv._blacklist_reason == {"slowhost": "slow"}
        assert drv._slow_strikes == {"slowhost": 2}
        assert drv._quarantine_strikes == {"deadhost": 1}
        assert "slowhost" in drv._blacklist
        # The quarantined host is excluded from allocation exactly as
        # before the crash.
        assert "slowhost" not in dict(drv._discover())
        # The notice survived, refreshed to the resumed driver's epoch
        # (same id: adopted workers keep their config).
        assert drv._replan_doc["id"] == 3
        assert drv._replan_doc["epoch"] == drv._epoch == 2
        raw = drv._kv.snapshot("elastic").get("replan")
        assert raw and json.loads(raw.decode())["epoch"] == 2
    finally:
        drv._kv.close()


def test_journal_v1_with_v2_records_refuses_loudly(tmp_path):
    """New records on an old-version document are mixed state: refuse
    rather than silently dropping (or trusting) them."""
    p = str(tmp_path / "driver_journal.json")
    with open(p, "w") as f:
        json.dump({"version": 1, "epoch": 2, "gen": 4,
                   "slow_strikes": {"h": 3}}, f)
    with pytest.raises(RuntimeError, match="v2 records.*slow_strikes"):
        DriverJournal(p).replay()
    with pytest.raises(RuntimeError):
        DriverJournal.open(p)


# ------------------------------------------------------------ re-plan
def _drifted_calibration(model, bw=0.05, lat=2.0):
    return Calibration(
        signature=model_signature(model),
        hops={
            model.hops[-1].name: {
                "calibrated": True,
                "latency_us": lat,
                "bandwidth_gbps": bw,
            }
        },
    )


def test_divergence_ratios_and_threshold():
    m = synthetic_model(2)
    calib = _drifted_calibration(m, bw=25.0, lat=2.0)  # 2x bw drift
    drifted, _ = calibrated_model(m, calib)
    ratios = sd.divergence_ratios(m, drifted)
    assert ratios["ici"] == pytest.approx(2.0)
    assert sd.max_divergence(ratios) == pytest.approx(1.0)
    assert sd.max_divergence(sd.divergence_ratios(m, m)) == 0.0


def test_skew_trend_needs_sustained_evidence():
    """The StepSkewTracker-trend trigger never fires on thin evidence:
    one noisy step is not a trend."""
    assert sd.skew_trend([0.5] * 3, min_n=8) is None
    assert sd.skew_trend([0.1] * 8, min_n=8) == pytest.approx(0.1)
    assert sd.skew_trend([0.0, 0.2] * 4, min_n=8) == pytest.approx(0.1)


def test_replay_divergence_skips_null_hops():
    rep = {"divergence": {"ici": 2.0, "dcn": None, "pod": 0.5}}
    out = sd.replay_divergence(rep)
    assert out == {"ici": 2.0, "pod": 2.0}  # symmetric, nulls skipped


def test_propose_replan_strictly_better_and_verified():
    m = synthetic_model(2)
    spec = ProgramSpec(name="t", layers=(("grad", 1 << 20),))
    calib = _drifted_calibration(m)
    prop = sd.propose_replan(spec, m, None, calib, drift=999.0)
    assert prop is not None
    assert prop.config["wire_dtype"] == "int8"
    assert prop.replanned_exposed_us < prop.current_exposed_us
    # The symbolic verifier clears every implied plan.
    assert sd.verify_replan(spec, prop.config, m, calib) == []
    # The incumbent being already optimal → no proposal (a re-plan that
    # does not strictly win is never published).
    again = sd.propose_replan(spec, m, prop.config, calib, drift=999.0)
    assert again is None


def test_replan_notice_shape_is_deterministic():
    m = synthetic_model(2)
    spec = ProgramSpec(name="t", layers=(("grad", 1 << 20),))
    calib = _drifted_calibration(m)
    a = sd.propose_replan(spec, m, None, calib).to_notice(1, 2, 3)
    b = sd.propose_replan(spec, m, None, calib).to_notice(1, 2, 3)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert (a["id"], a["gen"], a["epoch"]) == (1, 2, 3)


def test_worker_rejects_stale_epoch_and_stale_gen_notices():
    """Satellite: a re-plan notice is rejected on a stale epoch (fenced
    driver) or a stale generation — exactly once per notice id — while
    a FUTURE generation's notice is merely deferred."""
    from horovod_tpu.elastic import _ElasticContext

    ctx = _ElasticContext.__new__(_ElasticContext)
    ctx.epoch = 3
    ctx.gen = 2
    ctx.replan_id = 0
    ctx._replan_seen = 0
    ctx._pending_replan = None

    notices = {}
    ctx.fetch_replan = lambda strict=False: notices.get("doc")

    notices["doc"] = {"id": 1, "epoch": 2, "gen": 2, "config": {}}
    assert ctx.check_replan() is False          # stale epoch: rejected
    assert ctx._replan_seen == 1
    notices["doc"] = {"id": 2, "epoch": 3, "gen": 1, "config": {}}
    assert ctx.check_replan() is False          # stale generation
    assert ctx._replan_seen == 2
    notices["doc"] = {"id": 3, "epoch": 3, "gen": 5, "config": {}}
    assert ctx.check_replan() is False          # future gen: deferred...
    assert ctx._replan_seen == 2                # ...NOT marked examined
    ctx.gen = 5
    assert ctx.check_replan() is True           # adoptable after rejoin
    doc = ctx.take_pending_replan()
    assert doc["id"] == 3 and ctx.replan_id == 3
    # Idempotence: an already-adopted id is never re-examined.
    assert ctx.check_replan() is False


def test_adopted_step_kwargs_translation():
    import horovod_tpu.elastic as elastic

    prev = elastic._adopted_replan
    try:
        elastic._adopted_replan = {
            "id": 1, "gen": 1, "epoch": 1,
            "config": {
                "fusion_threshold_bytes": 1 << 22,
                "first_bucket_bytes": 1 << 20,
                "topo_algorithm": "two-level",
                "wire_dtype": "int8",
            },
        }
        kw = elastic.adopted_step_kwargs()
        assert kw == {
            "fusion_threshold_bytes": 1 << 22,
            "first_bucket_bytes": 1 << 20,
            "quantized": True,
            "hierarchical": "auto",
            "topo_algorithm": "two-level",
        }
        assert elastic.adopted_replan()["id"] == 1
    finally:
        elastic._adopted_replan = prev
    assert elastic.adopted_step_kwargs() is None or prev is not None


def test_spec_from_windows_and_env_override(monkeypatch):
    monkeypatch.delenv(sd.REPLAN_SPEC_ENV, raising=False)
    windows = {
        0: {"events": [
            {"name": "hvd_response", "ph": "X", "dur": 0.1,
             "args": {"tensor": "grad", "nbytes": 4096}},
            {"name": "hvd_response", "ph": "X", "dur": 0.1,
             "args": {"tensor": "grad", "nbytes": 8192}},
            {"name": "not_a_collective", "args": {"nbytes": 1}},
        ]},
    }
    spec = sd.spec_from_windows(windows)
    assert spec.layers == (("grad", 8192),)
    monkeypatch.setenv(
        sd.REPLAN_SPEC_ENV,
        json.dumps({"name": "pinned", "layers": [["l0", 123]]}),
    )
    spec = sd.spec_from_windows({})
    assert spec.name == "pinned" and spec.layers == (("l0", 123),)
    monkeypatch.setenv(sd.REPLAN_SPEC_ENV, "")
    assert sd.spec_from_windows({}) is None


def test_model_for_world_shapes():
    flat = sd.model_for_world({"assignments": {
        "a:0": {"rank": 0, "local_size": 1, "cross_size": 2},
        "b:0": {"rank": 1, "local_size": 1, "cross_size": 2},
    }})
    assert [h.name for h in flat.hops] == ["ici"] and flat.size == 2
    grid = sd.model_for_world({"assignments": {
        f"h{c}:{l}": {"rank": c * 2 + l, "local_size": 2, "cross_size": 2}
        for c in range(2) for l in range(2)
    }})
    assert [h.name for h in grid.hops] == ["dcn", "ici"]
    assert grid.size == 4


# -------------------------------------------------------- e2e scenario
# Shared with tools/selfdrive_smoke.py (the CI stage runs it twice and
# byte-diffs the normalized decision logs).
SELFDRIVE_SEED = 20260805
SELFDRIVE_STEPS = 14
SELFDRIVE_DELAY_S = 0.25

SELFDRIVE_WORKER = """
import os, sys, time
import numpy as np, jax
jax.config.update('jax_platforms', 'cpu')
import horovod_tpu as hvd
import horovod_tpu.elastic as elastic
from horovod_tpu import trace as hvd_trace
from horovod_tpu.fault import injector as fault_injector
hvd.init()   # a spare parks here until a generation claims its slot
import jax.numpy as jnp
print('START', hvd.rank(), os.getpid(), flush=True)
state = elastic.JaxState(w=np.zeros((4,), np.float32), step=0)

def local_phase(i):
    # The straggler surface: the seeded chronic delay (site step,
    # every=2 -> these explicit odd hits, not the commit-tap even hits)
    # stretches this span on the faulted rank only.
    fault_injector.step('selfdrive.step.%%d' %% i)
    time.sleep(0.05)

step_fn = hvd_trace.wrap_step(local_phase, wire_dtype='f32')

@elastic.run
def train(state):
    while state.step < %d:
        step_fn(state.step)
        g = hvd.allreduce(jnp.ones((4,), jnp.float32),
                          op=hvd.Average, name='grad')
        state.w = np.asarray(g) + np.asarray(state.w)
        state.step += 1
        time.sleep(0.15)
        state.commit()
    return state.step

train(state)
kw = elastic.adopted_step_kwargs() or {}
print('FINAL', hvd.rank(), hvd.size(), state.step,
      np.asarray(state.w, np.float32).tobytes().hex(),
      'quantized=%%s' %% int(bool(kw.get('quantized'))), flush=True)
hvd.shutdown()
""" % SELFDRIVE_STEPS


def selfdrive_fault_plan() -> dict:
    """Chronic slowness: rank 0 (the lone worker on host `localhost`)
    is delayed on every explicit step hit of generation 1 — the
    ``every``/``until`` recurring shape this PR adds."""
    return {
        "seed": SELFDRIVE_SEED,
        "faults": [
            {"kind": "delay", "rank": 0, "gen": 1, "site": "step",
             "seconds": SELFDRIVE_DELAY_S, "after": 0, "every": 2,
             "until": 4 * SELFDRIVE_STEPS},
        ],
    }


def write_drifted_calibration(path: str) -> str:
    """A calibration whose ICI constants drifted far from the generic
    defaults (the FlexLink 'measured reality') — signature-matched to
    the flat 2-rank model the driver prices re-plans on."""
    m = synthetic_model(2)
    calib = Calibration(
        signature=model_signature(m),
        hops={"ici": {"calibrated": True, "latency_us": 4.0,
                      "bandwidth_gbps": 0.05}},
        source="selfdrive-smoke",
    )
    save_calibration(calib, path)
    return path


DECISION_ACTIONS = (
    "quarantine", "replan", "replan-restamp", "replan-adopt",
    "promote", "spare-adopt",
)


def normalized_decisions(text: str):
    """The deterministic view of a self-driving run's event log: the
    DECISION ladder only (quarantine / re-plan / adopt / promote),
    sorted, seq dropped — worker-side delay counts depend on wall
    timing (the offender exits mid-window), decisions must not."""
    events = [json.loads(l) for l in text.splitlines() if l.strip()]
    return sorted(
        (e.get("rank") if e.get("rank") is not None else -1,
         e["site"], e["hit"], e["action"], e["detail"])
        for e in events if e["action"] in DECISION_ACTIONS
    )


def run_selfdrive_job(timeout: int = 240):
    """One seeded quarantine→re-plan→promote→recover run: 2 ranks over
    two 'hosts' (localhost + 127.0.0.1 — both local, no ssh) plus one
    hot spare; the chronic delay makes rank 0's host the sloth. Returns
    (proc, outs, decisions)."""
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as td:
        calib_path = write_drifted_calibration(
            os.path.join(td, "calibration.json")
        )
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_CYCLE_TIME": "1",
            "PYTHONPATH": os.pathsep.join(
                [repo, env.get("PYTHONPATH", "")]
            ).rstrip(os.pathsep),
            "HOROVOD_FAULT_PLAN": json.dumps(selfdrive_fault_plan()),
            "HOROVOD_FAULT_SEED": str(SELFDRIVE_SEED),
            "HOROVOD_FAULT_EVENT_LOG": os.path.join(
                td, "fault_events.jsonl"
            ),
            "HOROVOD_RPC_BACKOFF_BASE_S": "0.02",
            # Pin the universally-supported rejoin mode so the decision
            # log has ONE shape on every machine: respawn re-forms a
            # membership change in two publishes (drain notification,
            # then the post-drain restart that promotes the spare).
            "HOROVOD_ELASTIC_REJOIN_MODE": "respawn",
            # Observability plane the control loop feeds on.
            "HOROVOD_TRACE": "1",
            "HOROVOD_TRACE_PUSH_INTERVAL_S": "0.25",
            "HOROVOD_TRACE_STRAGGLER_THRESHOLD_S": "0.08",
            # The decision ladder under test.
            "HOROVOD_QUARANTINE_STRIKES": "3",
            "HOROVOD_QUARANTINE_WINDOW": "6",
            "HOROVOD_REPLAN_DIVERGENCE": "0.2",
            "HOROVOD_REPLAN_CHECK_S": "1",
            "HOROVOD_REPLAN_SPEC": json.dumps(
                {"name": "selfdrive", "layers": [["grad", 1 << 20]]}
            ),
            "HOROVOD_CALIBRATION_FILE": calib_path,
        })
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(SELFDRIVE_WORKER)
        args = [sys.executable, "-m", "horovod_tpu.run",
                "-np", "2", "-H", "localhost:1,127.0.0.1:2",
                "--min-np", "2", "--max-np", "2", "--spares", "1",
                "--output-dir", td, sys.executable, script]
        proc = subprocess.run(args, env=env, cwd=repo,
                              capture_output=True, timeout=timeout)
        outs = {}
        for fn in os.listdir(td):
            if fn.startswith("worker.") and (fn.endswith(".out")
                                             or fn.endswith(".err")):
                outs[fn] = open(os.path.join(td, fn),
                                errors="replace").read()
        for fn in ("driver.log", "fault_events.jsonl",
                   "driver_journal.json"):
            p = os.path.join(td, fn)
            if os.path.exists(p):
                outs[fn] = open(p, errors="replace").read()
        decisions = normalized_decisions(
            outs.get("fault_events.jsonl", "")
        )
        # Mid-run journal state: --resume mid-quarantine replays to the
        # same fleet verdicts (acceptance: chaos-proven determinism).
        jdoc = json.loads(outs["driver_journal.json"])
        outs["_journal"] = jdoc
    return proc, outs, decisions


def assert_selfdrive_recovery(proc, outs, decisions):
    import numpy as np

    stderr = proc.stderr.decode(errors="replace")
    assert proc.returncode == 0, (proc.returncode, stderr, outs)
    # The decision ladder fired, in full: one slowness quarantine of the
    # straggler's host; one re-plan published, then re-stamped for each
    # of respawn mode's two re-formation publishes (the gen-2 drain
    # notification and the gen-3 post-drain restart); one spare promoted
    # into gen 3; every member rank of gens 1 and 3 adopting.
    actions = [d[3] for d in decisions]
    assert actions.count("quarantine") == 1, decisions
    assert actions.count("replan") == 1, decisions
    assert actions.count("promote") == 1, decisions
    assert actions.count("replan-restamp") == 2, decisions
    assert actions.count("spare-adopt") == 1, decisions
    assert actions.count("replan-adopt") == 4, decisions  # 2 ranks x 2 gens
    q = next(d for d in decisions if d[3] == "quarantine")
    assert "host=localhost" in q[4] and "reason=slow" in q[4], decisions
    p = next(d for d in decisions if d[3] == "promote")
    assert "worker=127.0.0.1:1" in p[4] and p[2] == 3, decisions
    s = next(d for d in decisions if d[3] == "spare-adopt")
    assert s[0] == 1 and s[2] == 3, decisions  # joined gen 3 as rank 1
    # Both final ranks converged to the uninterrupted run's params,
    # bitwise, with the re-planned (int8-wire) step adopted.
    final_hex = np.full(
        4, float(SELFDRIVE_STEPS), np.float32
    ).tobytes().hex()
    finals = [l for o in outs.values() if isinstance(o, str)
              for l in o.splitlines() if l.startswith("FINAL")]
    assert len(finals) == 2, (finals, stderr)
    for line in finals:
        _, rank, size, step, whex, quant = line.split()
        assert size == "2" and step == str(SELFDRIVE_STEPS), finals
        assert whex == final_hex, (whex, final_hex)
        assert quant == "quantized=1", finals
    # Exactly four STARTs: the two gen-1 ranks, the survivor respawned
    # from its snapshot for gen 3, and the promoted spare (which starts
    # ONCE — promotion is a gate release, not a respawn).
    starts = [l for o in outs.values() if isinstance(o, str)
              for l in o.splitlines() if l.startswith("START")]
    assert len(starts) == 4, (starts, stderr)
    # The journal carries the verdicts a --resume would replay.
    jdoc = outs["_journal"]
    assert jdoc["slow_strikes"] == {"localhost": 1}, jdoc
    assert jdoc["blacklist_reasons"].get("localhost") == "slow", jdoc
    assert jdoc["replan"]["config"]["wire_dtype"] == "int8", jdoc
    # Modeled evidence: the re-planned config strictly beats the
    # incumbent on the drifted model (the sim-gated benefit).
    modeled = jdoc["replan"]["modeled"]
    assert (modeled["replanned_exposed_us"]
            < modeled["current_exposed_us"]), modeled


@pytest.mark.slow
def test_selfdrive_quarantine_replan_promote_e2e():
    """Acceptance (ISSUE 14): seeded chronic delay → slowness
    quarantine fires → hot spare promotes in the same generation bump →
    re-plan publishes and every rank adopts → training converges to the
    uninterrupted run's params. (CI runs this twice and byte-diffs the
    normalized decision logs: make selfdrive-smoke.)"""
    proc, outs, decisions = run_selfdrive_job()
    assert_selfdrive_recovery(proc, outs, decisions)
