"""Calibrated fleet simulator — the 256–4096-rank digital twin.

No fleet of hundreds of chips is at hand, yet the runtime
carries topology plans, a quantized wire, streamed ZeRO-1 and a tuner
whose wins are claimed *at scale*. This package makes those claims
observable from a CPU box by composing three models the repo already
trusts into one deterministic discrete-event simulation of a training
step (HiCCL-style analytic modeling, PAPERS.md arXiv:2408.05962,
promoted to a first-class evidence artifact):

- **Compute** — the structural-overlap staircase: backward compute is
  partitioned into the exact stream groups ``ops/fusion.
  plan_layer_groups`` would register (the same partition the tuner
  prices), each segment freeing its group's cotangents for the wire.
- **Communication** — every group's collective lowers through the real
  compositor (``topo/compositor.py``): the selected plan's per-stage
  alpha-beta costs are replayed hop by hop, hops modeled as serially
  shared resources, so two-level / split / int8 / ZeRO-1 RS+AG shapes
  price exactly as the planner prices them.
- **Faults** — stragglers come from seeded ``fault/plan.py`` schedules
  (``delay`` actions at the ``step`` site), drawn from the same
  per-(seed, action, rank) decision streams the chaos harness diffs,
  so a simulated incident is byte-reproducible.

Closing the loop both ways (the FlexLink lesson, arXiv:2510.15882 —
measure links, don't assume them):

- :mod:`sim.calibrate` fits per-hop alpha-beta constants from merged
  PR-10 trace data (``tools/trace_merge.py --stats``) into a
  signature-keyed ``calibration.json`` — same staleness-fallback
  discipline as ``tuned.json``: a calibration for a different hop
  ladder warns loudly and falls back to generation defaults.
- ``tools/fleet_sim.py --replay <trace-dir>`` re-simulates an observed
  run and reports per-hop model-vs-measured divergence as
  ``hvd_sim_divergence_ratio{hop}`` so a drifting model is loud, not
  silently wrong.

Simulated runs render as Perfetto traces through ``trace/merge.py``
(one lane per simulated rank, plan/fault instants preserved), so
predicted and observed timelines are inspected with the same tooling.

Everything here is deterministic and never touches an accelerator
backend (jax is imported only for the shared ``plan_layer_groups``
partition — one source of truth with the streamed path — and no device
is ever initialized): two runs from the same seed produce
byte-identical reports, the property ``make sim-smoke`` locks. See
docs/simulation.md.
"""

from __future__ import annotations

from .calibrate import (  # noqa: F401
    Calibration,
    apply_calibration,
    divergence_report,
    fit_calibration,
    load_calibration,
    measured_from_stats,
    model_signature,
    resolve_calibration,
    save_calibration,
)
from .core import (  # noqa: F401
    ServeSimConfig,
    SimConfig,
    SimGroup,
    SimProgram,
    SimResult,
    program_from_layers,
    program_from_spec,
    simulate,
    simulate_serve,
    straggler_sensitivity,
    tp_fixed_comm_us,
)
