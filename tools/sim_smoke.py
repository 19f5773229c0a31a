#!/usr/bin/env python
"""Fleet-simulator CI smoke (docs/simulation.md).

Four gates, mirroring how quant-smoke gates wire bytes:

1. DETERMINISM — two ``tools/fleet_sim.py`` predict runs over
   256/1024/4096 ranks are byte-identical (the evidence artifact is
   reproducible, like tuned.json / the topo plan dumps).
2. TWO-LEVEL BEATS FLAT AT SCALE — the compositor's headline claim is
   gated THROUGH the simulator: at 1024 simulated ranks the two-level
   lowering's step time is strictly below flat's.
3. REAL-TRACE REPLAY — a real 2-rank CPU job through the elastic
   driver with HOROVOD_TRACE=1 produces merged trace windows;
   ``trace_merge.py --stats`` summarizes them and ``fleet_sim.py
   --replay`` re-simulates the observed run, reporting finite,
   bounded per-hop divergence ratios (the drift alarm works on real
   data end to end).
4. CALIBRATION LOOP — a calibration fitted from a simulated trace
   with known constants recovers them, and replaying under it yields
   per-hop divergence ~1.

Exit 0 = all assertions hold. Wired as tools/ci_checks.sh stage 12
(skip: HVD_CI_SKIP_SIM=1) and ``make sim-smoke``. Budget: ~30s CPU
(the 2-rank job dominates).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

STEPS = 8

WORKER = """
    import os, time
    import numpy as np
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import horovod_tpu as hvd
    from horovod_tpu import trace as hvd_trace

    hvd.init()
    assert hvd.size() == 2
    assert hvd_trace.ACTIVE

    def train_step(i):
        time.sleep(0.01)
        out = np.asarray(hvd.allreduce(
            np.ones(65536, np.float32), name=f'sim.grad.{i}',
            op=hvd.Sum))
        assert out[0] == hvd.size()

    step = hvd_trace.wrap_step(train_step, wire_dtype='f32')
    for i in range(%(steps)d):
        step(i)
    time.sleep(3.0)  # window for the driver's trace collection
    print('SIM_WORKER_DONE', hvd.rank(), flush=True)
    hvd.shutdown()
""" % {"steps": STEPS}


def _run(cmd, **kw):
    proc = subprocess.run(
        cmd, cwd=REPO, capture_output=True, **kw
    )
    assert proc.returncode == 0, (
        f"{' '.join(cmd)} failed rc={proc.returncode}\n"
        f"{proc.stdout.decode(errors='replace')}\n"
        f"{proc.stderr.decode(errors='replace')}"
    )
    return proc


def gate_determinism(td: str) -> dict:
    outs = []
    for tag in ("a", "b"):
        out = os.path.join(td, f"predict_{tag}.json")
        _run([
            sys.executable, "tools/fleet_sim.py",
            "--ranks", "256", "1024", "4096", "--program",
            "transformer", "--steps", "2", "--seed", "0", "-o", out,
        ])
        with open(out, "rb") as f:
            outs.append(f.read())
    assert outs[0] == outs[1], (
        "fleet_sim predict runs are not byte-identical"
    )
    return json.loads(outs[0].decode())


def gate_two_level_beats_flat() -> dict:
    from horovod_tpu.sim import SimConfig, program_from_layers, simulate
    from horovod_tpu.topo.model import synthetic_model

    model = synthetic_model(8, cross=128)  # 1024 ranks
    prog = program_from_layers(
        "gate", [4 << 20] * 8, first_bucket_bytes=1 << 20,
    )
    flat = simulate(model, prog, SimConfig(algorithm="flat"), steps=2)
    two = simulate(
        model, prog, SimConfig(algorithm="two-level"), steps=2
    )
    assert two.mean_step_us < flat.mean_step_us, (
        f"two-level ({two.mean_step_us}us) must strictly beat flat "
        f"({flat.mean_step_us}us) at 1024 simulated ranks"
    )
    return {
        "flat_us": round(flat.mean_step_us, 1),
        "two_level_us": round(two.mean_step_us, 1),
    }


def gate_real_trace_replay(td: str) -> dict:
    env = dict(os.environ)
    trace_dir = os.path.join(td, "trace")
    env.update({
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "1",
        "HOROVOD_TRACE": "1",
        "HOROVOD_TRACE_DIR": trace_dir,
        "HOROVOD_TRACE_PUSH_INTERVAL_S": "0.25",
        "PYTHONPATH": os.pathsep.join(
            [REPO, env.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep),
    })
    script = os.path.join(td, "worker.py")
    with open(script, "w") as f:
        f.write(textwrap.dedent(WORKER))
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run",
         "-np", "2", "--min-np", "2", "--max-np", "2",
         "--output-dir", td, sys.executable, script],
        env=env, cwd=REPO, capture_output=True, timeout=90,
    )
    text = proc.stdout.decode(errors="replace")
    for fn in sorted(os.listdir(td)):
        if fn.startswith("worker.") and fn.endswith((".out", ".err")):
            with open(os.path.join(td, fn), errors="replace") as f:
                text += f"\n--- {fn} ---\n" + f.read()
    assert proc.returncode == 0, (
        f"2-rank traced job failed rc={proc.returncode}\n{text}\n"
        f"{proc.stderr.decode(errors='replace')}"
    )
    assert "SIM_WORKER_DONE 0" in text and "SIM_WORKER_DONE 1" in text

    # --stats over the driver-collected windows (byte-stable: run it
    # twice and diff).
    stats_path = os.path.join(td, "stats.json")
    _run([sys.executable, "tools/trace_merge.py", trace_dir,
          "--stats", "-o", stats_path])
    with open(stats_path, "rb") as f:
        stats_a = f.read()
    _run([sys.executable, "tools/trace_merge.py", trace_dir,
          "--stats", "-o", stats_path])
    with open(stats_path, "rb") as f:
        stats_b = f.read()
    assert stats_a == stats_b, "--stats output is not byte-stable"
    stats = json.loads(stats_a.decode())
    assert stats["world_size"] == 2
    assert stats["ranks"]["0"]["step_count"] >= STEPS - 1
    samples = sum(
        len(stats["ranks"][r]["collectives"]) for r in stats["ranks"]
    )
    assert samples > 0, "no collective samples in the real trace"

    # Replay: re-simulate the observed run; per-hop divergence must be
    # present, finite, and bounded (generic constants vs a CPU
    # loopback "fabric" — the gate is that the drift ALARM works, not
    # that the defaults match localhost).
    replay_path = os.path.join(td, "replay.json")
    _run([sys.executable, "tools/fleet_sim.py",
          "--replay", trace_dir, "-o", replay_path])
    with open(replay_path) as f:
        replay = json.load(f)
    per_hop = replay["divergence"]["per_hop"]
    assert per_hop, "replay reported no per-hop divergence"
    for hop, entry in per_hop.items():
        r = entry["ratio"]
        assert r is not None and 1e-6 < r < 1e6, (hop, entry)
    step_ratio = replay["divergence"]["step"]["ratio"]
    assert step_ratio is not None and 1e-6 < step_ratio < 1e6
    return {
        "steps": stats["ranks"]["0"]["step_count"],
        "samples": samples,
        "hops": sorted(per_hop),
        "step_ratio_bounded": True,
    }


def gate_calibration_loop(td: str) -> dict:
    from horovod_tpu.sim import (
        SimConfig,
        load_calibration,
        simulate,
    )
    from horovod_tpu.sim.core import SimGroup, SimProgram
    from horovod_tpu.topo.model import synthetic_model

    model = synthetic_model(4, cross=2)
    prog = SimProgram(
        name="cal",
        groups=(SimGroup("g0", 2 << 20, 200.0),
                SimGroup("g1", 1 << 20, 200.0),
                SimGroup("g2", 512 << 10, 100.0)),
        forward_us=200.0, optimizer_us=20.0,
    )
    res = simulate(model, prog, SimConfig(), steps=3)
    tdir = os.path.join(td, "simtrace")
    os.makedirs(tdir, exist_ok=True)
    for r, doc in res.windows().items():
        with open(os.path.join(tdir, f"rank.{r}.json"), "w") as f:
            json.dump(doc, f, sort_keys=True)
    with open(os.path.join(tdir, "driver.json"), "w") as f:
        json.dump(res.driver_window(), f, sort_keys=True)
    calib_path = os.path.join(td, "calibration.json")
    _run([sys.executable, "tools/fleet_sim.py",
          "--calibrate", tdir, "--local", "4", "-o", calib_path])
    calib = load_calibration(calib_path)
    for h in model.hops:
        entry = calib.hops[h.name]
        assert entry["calibrated"], calib.hops
        assert abs(entry["bandwidth_gbps"] - h.bandwidth_gbps) < (
            0.01 * h.bandwidth_gbps
        ), (h.name, entry)
    replay_path = os.path.join(td, "replay_cal.json")
    _run([sys.executable, "tools/fleet_sim.py",
          "--replay", tdir, "--local", "4",
          "--calibration", calib_path, "-o", replay_path])
    with open(replay_path) as f:
        replay = json.load(f)
    assert replay["calibration"]["applied"] is True
    for hop, entry in replay["divergence"]["per_hop"].items():
        assert abs(entry["ratio"] - 1.0) < 0.05, (hop, entry)
    return {
        "recovered_hops": sorted(calib.hops),
        "replay_calibrated": True,
    }


def main() -> int:
    t0 = time.time()
    td = tempfile.mkdtemp(prefix="sim_smoke_")
    report = gate_determinism(td)
    effs = {
        str(r["ranks"]): r["scaling_efficiency"]
        for r in report["results"]
    }
    scale = gate_two_level_beats_flat()
    loop = gate_calibration_loop(td)
    replay = gate_real_trace_replay(td)
    print(
        f"[sim-smoke] OK in {time.time() - t0:.1f}s: predict "
        f"byte-stable (eff {effs}), two-level {scale['two_level_us']}us "
        f"< flat {scale['flat_us']}us at 1024 ranks, calibration "
        f"recovered {loop['recovered_hops']} with replay ratios ~1, "
        f"real 2-rank replay bounded over {replay['samples']} samples "
        f"({replay['steps']} steps, hops {replay['hops']})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
