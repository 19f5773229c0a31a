"""Elastic training: fault tolerance + dynamically changing membership.

Later-reference parity (``horovod.elastic``, added upstream in v0.20 — not
present in the v0.18.2 reference tree, like the ProcessSet and grouped-op
APIs this build already ships): a training loop wrapped in
``@hvd.elastic.run`` survives worker failures and host set changes by
rolling back to the last committed ``State`` and re-forming the world with
the surviving/new workers.

TPU-native design — generation-based world re-formation, no process
restart:

- The elastic driver (``hvdrun --min-np/--max-np/--host-discovery-script``,
  ``run/elastic_driver.py``) publishes each world *generation* (membership,
  rank assignments, and FRESH control-plane + JAX-coordinator endpoints) in
  its HTTP KV rendezvous store.
- Workers re-rendezvous IN PROCESS: tear down the JAX distributed client
  and the XLA backend caches (``jax.distributed.shutdown()`` +
  ``xla_bridge._clear_backends()``), update the ``HOROVOD_*`` env from the
  new generation, and ``hvd.init()`` again. Weights stay in host memory
  (the committed ``State``); nothing is re-spawned, so recovery cost is one
  re-rendezvous + one recompilation at the new world size.
- ``State.check_host_updates()`` reaches cross-rank agreement with a tiny
  allreduce before interrupting, so every live rank raises
  ``HostsUpdatedInterrupt`` at the same step (upstream's notification
  agreement, re-expressed as the collective it always was).

Failure semantics: a crashed peer surfaces on survivors as
``HorovodInternalError`` (transport abort or stall shutdown) → ``run``
restores the last commit and rejoins the next generation. A graceful
membership change (host added/removed by discovery) surfaces as
``HostsUpdatedInterrupt`` → current in-memory state is KEPT (no rollback)
and re-synced from the new rank 0.
"""

from __future__ import annotations

import contextlib
import copy
import functools
import json
import logging
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from .. import guard as _guard
from .. import metrics as _metrics
from .. import trace as _trace
from ..fault import injector as _fault_injector
from ..fault import preemption as _preemption
from ..fault.preemption import PreemptionInterrupt  # noqa: F401 (re-export)

logger = logging.getLogger("horovod_tpu.elastic")

__all__ = [
    "run",
    "State",
    "ObjectState",
    "JaxState",
    "TorchState",
    "TensorFlowState",
    "TensorFlowKerasState",
    "HostsUpdatedInterrupt",
    "PlanUpdatedInterrupt",
    "PreemptionInterrupt",
    "adopted_replan",
    "adopted_step_kwargs",
    "apply_serve_scale",
    "note_zero1_layout",
]


class HostsUpdatedInterrupt(Exception):
    """Raised inside the training function when the driver published a new
    world generation (host added/removed). The in-memory state is kept;
    ``run`` re-rendezvouses and re-syncs it."""


class PlanUpdatedInterrupt(Exception):
    """Raised inside the training function — on EVERY rank, at the same
    commit boundary (the adoption rides the host-check agreement
    allreduce) — when the driver published a live re-plan notice
    (docs/fault_tolerance.md "Self-driving fleet"). The world is
    unchanged: no rollback, no re-rendezvous; ``run`` re-enters the
    training function so it rebuilds its step from
    :func:`adopted_step_kwargs` (a ``make_train_step`` rebuilt from
    ``tune.tuned_step_kwargs`` — never a mid-step knob flip)."""

    def __init__(self, notice: Dict[str, Any]):
        self.notice = dict(notice)
        super().__init__(
            f"live re-plan #{notice.get('id')} adopted: "
            f"{notice.get('config')}"
        )


# --------------------------------------------------------------- context
class _ElasticContext:
    """Worker-side view of the elastic rendezvous (driver KV store)."""

    def __init__(self) -> None:
        from ..run.http_server import KVStoreClient

        self.worker_id = os.environ["HOROVOD_ELASTIC_WORKER_ID"]
        self.gen = int(os.environ.get("HOROVOD_ELASTIC_GEN", "1"))
        # Driver-epoch fencing baseline (docs/fault_tolerance.md
        # "Control-plane availability"): the incarnation of the driver
        # that spawned this worker. A resumed driver presents a HIGHER
        # epoch (reattach); anything lower is a stale driver that lost a
        # supervisor race and must be rejected.
        try:
            self.epoch = int(os.environ.get("HOROVOD_DRIVER_EPOCH", "0"))
        except ValueError:
            self.epoch = 0
        # Rank holding the authoritative state for the current generation
        # (a survivor after a re-formation; see ElasticDriver._publish).
        # From env at spawn (a respawned worker joins mid-job and never
        # goes through apply() for its first generation), then updated by
        # apply() on every re-formation.
        self.sync_root = int(
            os.environ.get("HOROVOD_ELASTIC_SYNC_ROOT", "0")
        )
        addr = os.environ["HOROVOD_ELASTIC_KV_ADDR"]
        port = int(os.environ["HOROVOD_ELASTIC_KV_PORT"])
        self._kv = KVStoreClient(addr, port)
        self.timeout = float(
            os.environ.get("HOROVOD_ELASTIC_TIMEOUT", "600")
        )
        # Consecutive failed control-plane probes; at the threshold the
        # driver is declared lost and this rank votes to park.
        self._probe_failures = 0
        try:
            self.lost_threshold = max(1, int(os.environ.get(
                "HOROVOD_DRIVER_LOST_PROBES", "3")))
        except ValueError:
            self.lost_threshold = 3
        self._parks = 0
        # Live re-plan bookkeeping (docs/fault_tolerance.md
        # "Self-driving fleet"): the last ADOPTED notice id, the last
        # EXAMINED id (so a rejected stale notice is not re-litigated
        # every commit), and the validated doc awaiting the commit-
        # boundary agreement.
        self.replan_id = 0
        self._replan_seen = 0
        self._pending_replan: Optional[Dict[str, Any]] = None

    def fetch_world(self, strict: bool = False) -> Optional[Dict[str, Any]]:
        raw = self._kv.get("elastic", "world", strict=strict)
        if raw is None:
            return None
        return json.loads(raw.decode())

    def fetch_driver(self, strict: bool = False) -> Optional[Dict[str, Any]]:
        """The driver's identity doc on the KV plane: epoch (fencing
        token), generation, liveness beat."""
        raw = self._kv.get("elastic", "driver", strict=strict)
        if raw is None:
            return None
        return json.loads(raw.decode())

    def fetch_replan(self, strict: bool = False) -> Optional[Dict[str, Any]]:
        """The driver's live re-plan notice, if one is published."""
        raw = self._kv.get("elastic", "replan", strict=strict)
        if raw is None:
            return None
        doc = json.loads(raw.decode())
        return doc if isinstance(doc, dict) else None

    def check_replan(self) -> bool:
        """Examine the published re-plan notice (one KV read per
        commit). A fresh, valid notice is stashed for the commit-
        boundary agreement; a STALE one — epoch below this worker's
        fencing baseline (a fenced driver's plans are as untrustworthy
        as its worlds) or a generation that is not the current one — is
        rejected loudly, exactly once per notice id. Returns True while
        a validated notice awaits adoption."""
        try:
            doc = self.fetch_replan()
        except Exception:  # noqa: BLE001 - driver briefly unreachable
            return self._pending_replan is not None
        if not doc:
            return self._pending_replan is not None
        try:
            nid = int(doc.get("id", 0))
            epoch = int(doc.get("epoch", 0) or 0)
            gen = int(doc.get("gen", -1))
        except (TypeError, ValueError):
            return self._pending_replan is not None
        if nid <= self.replan_id or nid <= self._replan_seen:
            return self._pending_replan is not None
        if gen > self.gen:
            # Stamped for a generation this worker has not joined yet
            # (the driver re-stamps notices across re-formations): not
            # stale, just early — leave it unexamined; it becomes
            # adoptable right after the rejoin commits the new gen.
            return self._pending_replan is not None
        reason = None
        if epoch < self.epoch:
            reason = "stale-epoch"
        elif gen < self.gen:
            reason = "stale-generation"
        if reason is not None:
            self._replan_seen = nid
            if _metrics.ACTIVE:
                _metrics.TAP.inc("hvd_replan_rejected_total",
                                 reason=reason)
            logger.error(
                "elastic: rejecting re-plan notice #%s (%s: notice "
                "epoch %s / gen %s vs acknowledged epoch %s / current "
                "gen %s)", nid, reason, epoch, gen, self.epoch, self.gen,
            )
            return self._pending_replan is not None
        self._replan_seen = nid
        self._pending_replan = doc
        return True

    def take_pending_replan(self) -> Dict[str, Any]:
        """The notice to adopt after the fleet AGREED at a commit
        boundary. A rank whose own KV read raced the publish (it got
        the agreement bit from a peer) re-fetches here; if the notice
        is unreachable the adoption cannot be completed consistently
        and the caller degrades to the rollback path."""
        doc = self._pending_replan
        if doc is None:
            for _ in range(3):
                try:
                    doc = self.fetch_replan(strict=True)
                except Exception:  # noqa: BLE001 - retried below
                    doc = None
                if doc:
                    break
                time.sleep(0.2)
        if doc is None:
            import horovod_tpu as hvd

            raise hvd.HorovodInternalError(
                "elastic: the fleet agreed to adopt a re-plan notice "
                "this rank cannot fetch; rolling back to stay consistent"
            )
        self._pending_replan = None
        self.replan_id = max(self.replan_id, int(doc.get("id", 0)))
        return doc

    def probe_driver(self):
        """One strict probe of the control plane for the park loop:
        (driver_doc, world_doc), or (None, None) while the driver is
        unreachable."""
        try:
            return self.fetch_driver(strict=True), self.fetch_world(
                strict=True
            )
        except Exception:  # noqa: BLE001 - endpoint down
            return None, None

    def commit_probe(self):
        """Per-commit control-plane probe. Returns
        ``(updated, driver_lost, new_epoch)``:

        - ``updated`` — a newer world generation is published;
        - ``driver_lost`` — ``lost_threshold`` consecutive probes failed
          (dead driver), or the plane is served by a STALE driver epoch
          (split brain — park and wait to be fenced through);
        - ``new_epoch`` — the driver restarted (epoch advanced) while
          publishing the SAME generation: the fleet never broke, so this
          rank can reattach in place, no parking and no collective."""
        try:
            world = self.fetch_world(strict=True)
            driver = self.fetch_driver(strict=True)
        except Exception:  # noqa: BLE001 - endpoint down
            self._probe_failures += 1
            return False, self._probe_failures >= self.lost_threshold, None
        self._probe_failures = 0
        updated = bool(world) and int(world["gen"]) > self.gen
        if driver is not None:
            try:
                epoch = int(driver.get("epoch", 0) or 0)
            except (TypeError, ValueError):
                epoch = 0
            if epoch < self.epoch:
                # A fenced driver's world/generation claims are not
                # trustworthy either: treat as loss, the park loop keeps
                # rejecting it until a current driver answers.
                if _metrics.ACTIVE:
                    _metrics.TAP.inc("hvd_worker_driver_fenced_total")
                return False, True, None
            if (epoch > self.epoch and not updated
                    and world is not None
                    and int(world["gen"]) == self.gen):
                return False, False, epoch
        return updated, False, None

    def reattach(self, epoch: int) -> None:
        """Adopt the resumed driver: accept its (higher) epoch,
        re-register under it, and carry on — same generation, same
        process, no rollback."""
        self.epoch = int(epoch)
        self._probe_failures = 0
        self.signal_attach()
        if _trace.ACTIVE:
            _trace.TAP.event(
                "hvd_worker_reattach", cat="elastic",
                gen=self.gen, epoch=self.epoch,
            )
        if _metrics.ACTIVE:
            _metrics.TAP.inc("hvd_worker_reattaches_total")
        if _fault_injector.ACTIVE:
            _fault_injector.record_event(
                "driver", 1, "reattach",
                f"gen={self.gen} epoch={self.epoch}",
            )
        logger.warning(
            "elastic: reattached to resumed driver (generation %s, "
            "epoch %s)", self.gen, self.epoch,
        )

    def signal_attach(self) -> None:
        """Re-register with a resumed driver: the adoption machinery
        (ElasticDriver._poll_adopted) matches the generation + epoch and
        uses the pid for local liveness supervision."""
        try:
            self._kv.put(
                "elastic", f"attach.{self.worker_id}",
                f"{self.gen}:{self.epoch}:{os.getpid()}".encode(),
            )
        except Exception:  # noqa: BLE001 - advisory signal
            pass

    def signal_done(self) -> None:
        """Tell the driver this worker's training function returned.
        A resumed driver has no process handle on adopted workers, so a
        clean exit would otherwise be invisible to it."""
        try:
            self._kv.put(
                "elastic", f"done.{self.worker_id}",
                str(self.gen).encode(),
            )
        except Exception:  # noqa: BLE001 - advisory signal
            pass

    def confirm_joined(self) -> None:
        """Tell the driver this worker completed a state sync in its
        current generation — from then on it holds live training state
        and is a valid sync_root for future re-formations."""
        try:
            self._kv.put(
                "elastic", f"joined.{self.worker_id}",
                str(self.gen).encode(),
            )
        except Exception:  # noqa: BLE001 - advisory signal
            pass

    def signal_rejoin(self) -> None:
        """Tell the driver this worker abandoned its current generation
        (rollback with every process still alive — stall shutdown,
        transient control-plane error). The driver responds by bumping
        the generation even though membership did not change; without
        this, every rank would wait out the full elastic timeout for a
        bump that nothing else triggers."""
        try:
            self._kv.put(
                "elastic", f"rejoin.{self.worker_id}",
                str(self.gen).encode(),
            )
        except Exception:  # noqa: BLE001 - advisory signal
            pass

    def poll_updated(self) -> bool:
        """True when the driver has published a newer generation than the
        one this worker is part of."""
        try:
            world = self.fetch_world()
        except Exception:  # noqa: BLE001 - driver briefly unreachable
            return False
        return bool(world) and int(world["gen"]) > self.gen

    def apply(self, world: Dict[str, Any]) -> bool:
        """Point the ``HOROVOD_*`` env at this generation's assignment.
        Returns False when this worker is not a member of the new world.
        Deliberately does NOT advance ``self.gen`` — the caller commits
        the generation only after ``hvd.init()`` succeeds, so a transient
        init failure retries the SAME still-live generation instead of
        waiting forever for a bump the driver has no reason to publish."""
        a = world["assignments"].get(self.worker_id)
        if a is None:
            return False
        os.environ.update(
            {
                "HOROVOD_RANK": str(a["rank"]),
                "HOROVOD_SIZE": str(world["size"]),
                "HOROVOD_LOCAL_RANK": str(a["local_rank"]),
                "HOROVOD_LOCAL_SIZE": str(a["local_size"]),
                "HOROVOD_CROSS_RANK": str(a["cross_rank"]),
                "HOROVOD_CROSS_SIZE": str(a["cross_size"]),
                "HOROVOD_CONTROLLER_ADDR": world["controller_addr"],
                "HOROVOD_CONTROLLER_PORT": str(world["controller_port"]),
                "HOROVOD_JAX_COORDINATOR": world["jax_coordinator"],
                "HOROVOD_ELASTIC_GEN": str(world["gen"]),
            }
        )
        self.sync_root = int(world.get("sync_root", 0))
        # The generation doc is epoch-stamped: joining it acknowledges
        # its driver, raising this worker's fencing baseline.
        try:
            self.epoch = max(self.epoch, int(world.get("epoch", 0) or 0))
        except (TypeError, ValueError):
            pass
        return True


_context: Optional[_ElasticContext] = None


def _ctx() -> Optional[_ElasticContext]:
    global _context
    if _context is None and os.environ.get("HOROVOD_ELASTIC") == "1":
        _context = _ElasticContext()
    return _context


# ------------------------------------------- driver-loss park/reattach
class DriverWatch:
    """Pure classification core of the worker-side park/reconnect state
    machine (unit-testable without a fleet): given what a parked rank
    currently observes on the KV plane, decide its next move.

    - ``wait``     — no driver answering (or no world yet): keep parking.
    - ``fenced``   — a driver is answering but with an epoch LOWER than
      one this worker has already acknowledged: a stale incarnation that
      lost a supervisor race. Rejected; keep parking for the real one.
    - ``reattach`` — a current-or-newer epoch republished the SAME
      generation this rank is part of: the fleet never broke, resume in
      place (``epoch_seen`` carries the epoch to adopt).
    - ``rejoin``   — the returning driver published a DIFFERENT
      generation: this rank's world is gone; degrade to the existing
      membership-interrupt path (state kept, re-sync, or respawn)."""

    def __init__(self, gen: int, epoch: int):
        self.gen = int(gen)
        self.epoch = int(epoch)
        self.epoch_seen: Optional[int] = None
        self.fenced = 0

    def classify(self, driver_doc, world_doc) -> str:
        if not isinstance(driver_doc, dict):
            return "wait"
        try:
            epoch = int(driver_doc.get("epoch", 0) or 0)
        except (TypeError, ValueError):
            return "wait"
        if epoch < self.epoch:
            self.fenced += 1
            return "fenced"
        if not isinstance(world_doc, dict):
            return "wait"
        try:
            gen = int(world_doc.get("gen", -1))
        except (TypeError, ValueError):
            return "wait"
        if gen == self.gen:
            self.epoch_seen = epoch
            return "reattach"
        return "rejoin"


# Cross-rank outcome agreement codes, ordered by severity (the fleet
# adopts the MAX so no rank resumes into a world a peer abandoned).
PARK_OUTCOMES = {"reattach": 0, "rejoin": 1, "dead": 2}


def _park_and_reattach(ctx: _ElasticContext, state=None) -> None:
    """Driver-loss handling, entered at a commit boundary once the fleet
    AGREED (via the host-check allreduce) that the driver is gone:
    training state is held, collectives are quiesced, and every rank
    polls the KV plane with the bounded-backoff machinery until a
    current-epoch driver answers. Same generation back → reattach in
    place; new generation → the existing rollback/rejoin path; no driver
    within the elastic timeout → collective failure (rollback, and in
    respawn mode persist-and-exit so a future driver finds the
    snapshots)."""
    import numpy as np

    import horovod_tpu as hvd

    from ..fault.backoff import Backoff

    ctx._parks += 1
    if _trace.ACTIVE:
        _trace.TAP.event(
            "hvd_worker_park", cat="elastic", gen=ctx.gen, epoch=ctx.epoch,
        )
    if _metrics.ACTIVE:
        _metrics.TAP.inc("hvd_worker_parks_total")
    if _fault_injector.ACTIVE:
        _fault_injector.record_event(
            "driver", ctx._parks, "park", f"gen={ctx.gen}"
        )
    logger.warning(
        "elastic: driver unreachable; parked at the commit boundary "
        "(state held, collectives quiesced; gen %s, epoch %s)",
        ctx.gen, ctx.epoch,
    )
    watch = DriverWatch(ctx.gen, ctx.epoch)
    backoff = Backoff.from_env()
    deadline = time.monotonic() + ctx.timeout
    attempt = 0
    fenced_logged = False
    outcome = "dead"
    while time.monotonic() <= deadline:
        driver_doc, world_doc = ctx.probe_driver()
        got = watch.classify(driver_doc, world_doc)
        if got in ("reattach", "rejoin"):
            outcome = got
            break
        if got == "fenced":
            if _metrics.ACTIVE:
                _metrics.TAP.inc("hvd_worker_driver_fenced_total")
            if not fenced_logged:
                fenced_logged = True
                if _fault_injector.ACTIVE:
                    _fault_injector.record_event(
                        "driver", ctx._parks, "fenced",
                        f"epoch={driver_doc.get('epoch')}<{ctx.epoch}",
                    )
                logger.error(
                    "elastic: rejecting stale driver (epoch %s < "
                    "acknowledged %s); waiting for a current one",
                    driver_doc.get("epoch"), ctx.epoch,
                )
        time.sleep(backoff.delay(min(attempt, 5)))
        attempt += 1
    # Outcome agreement: a rank must not resume into a world a peer has
    # abandoned (or vice versa) — adopt the most severe observation.
    code = PARK_OUTCOMES[outcome]
    if hvd.is_initialized() and hvd.size() > 1:
        agreed = int(np.asarray(hvd.allreduce(
            np.asarray([code], np.int32), op=hvd.Max,
            name="hvd.elastic.parkagree",
        ))[0])
    else:
        agreed = code
    if agreed == PARK_OUTCOMES["reattach"]:
        ctx.reattach(watch.epoch_seen if watch.epoch_seen is not None
                     else ctx.epoch)
        return
    if agreed == PARK_OUTCOMES["rejoin"]:
        raise HostsUpdatedInterrupt(
            "driver resumed with a new world generation; rejoining"
        )
    raise hvd.HorovodInternalError(
        f"elastic: no current driver within {ctx.timeout:g}s of parking "
        f"(last known generation {ctx.gen}, epoch {ctx.epoch})"
    )


# ------------------------------------------------------- live re-plan
_adopted_replan: Optional[Dict[str, Any]] = None


def _adopt_replan(ctx: _ElasticContext) -> None:
    """Commit-boundary re-plan adoption, after the fleet AGREED via the
    host-check allreduce: record the notice, then interrupt the training
    function so it rebuilds its step — a generation-style state
    transition (state kept, no rollback, no re-rendezvous), never a
    mid-step knob flip."""
    global _adopted_replan
    doc = ctx.take_pending_replan()
    _adopted_replan = doc
    if _metrics.ACTIVE:
        _metrics.TAP.inc("hvd_replan_adoptions_total")
    if _trace.ACTIVE:
        _trace.TAP.event(
            "hvd_replan_adopt", cat="elastic",
            id=int(doc.get("id", 0)), gen=ctx.gen,
        )
    # The new plan invalidates the noted correlation ids; the
    # rebuilt step re-notes its own.
    _trace.note_plan(
        topo_algorithm=doc.get("config", {}).get("topo_algorithm"),
        wire_dtype=doc.get("config", {}).get("wire_dtype"),
    )
    if _fault_injector.ACTIVE:
        _fault_injector.record_event(
            "driver", int(doc.get("id", 0)), "replan-adopt",
            f"id={doc.get('id')}",
        )
    logger.warning(
        "elastic: adopting live re-plan #%s at the commit boundary "
        "(%s); rebuilding the train step", doc.get("id"),
        doc.get("config"),
    )
    raise PlanUpdatedInterrupt(doc)


def adopted_replan() -> Optional[Dict[str, Any]]:
    """The last live re-plan notice this worker adopted (None before
    any). Plain data: ``{"id", "gen", "epoch", "trigger", "config",
    "modeled", ...}``."""
    return dict(_adopted_replan) if _adopted_replan else None


def adopted_step_kwargs() -> Optional[Dict[str, Any]]:
    """The ``make_train_step`` knob values the adopted re-plan maps to,
    via the SAME ``tune.tuned_step_kwargs`` translation a pinned
    ``tuned.json`` uses — so a re-planned step is bitwise-identical to
    the same knobs passed by hand. None before any adoption; training
    loops splat it when (re)building their step:

    .. code-block:: python

        kwargs = hvd.elastic.adopted_step_kwargs() or {}
        step = hvd.make_train_step(loss_fn, opt, **kwargs)
    """
    if _adopted_replan is None:
        return None
    from ..tune import TunedConfig, tuned_step_kwargs

    cfg = TunedConfig(
        knobs=dict(_adopted_replan.get("config") or {}),
        signature={}, objectives={}, baseline={},
        program="live-replan",
    )
    return tuned_step_kwargs(cfg)


# --------------------------------------------------------- hot spares
SPARE_POLL_S = 0.5


def maybe_wait_as_spare() -> bool:
    """The spare gate (docs/fault_tolerance.md "Self-driving fleet"):
    a worker spawned with ``HOROVOD_ELASTIC_SPARE=1`` holds HERE —
    before any backend or rank plumbing exists — heartbeating
    ``spare.<wid>`` on the KV plane until the driver's EXPLICIT
    ``promote.<wid>`` signal names a generation whose published world
    assigns this worker id. (The world doc alone is not enough: in
    respawn mode the first publish after a membership change is only
    the drain notification — joining it would wedge the spare on a
    doomed generation's endpoints.) Promotion applies the assignment
    env exactly like a re-rendezvous and returns True; ``hvd.init()``
    then proceeds as a normal member of that generation (the driver
    counted one generation bump, not a respawn).

    Exit conditions: the driver stops answering for the elastic timeout
    (fleet gone → exit 0), or a NEWER driver epoch appears (a resumed
    driver respawns its own spares; a stale pool must not race it for
    slots → exit 0)."""
    if os.environ.get("HOROVOD_ELASTIC_SPARE") != "1":
        return False
    from ..run.http_server import KVStoreClient

    wid = os.environ["HOROVOD_ELASTIC_WORKER_ID"]
    addr = os.environ["HOROVOD_ELASTIC_KV_ADDR"]
    port = int(os.environ["HOROVOD_ELASTIC_KV_PORT"])
    try:
        spawn_epoch = int(os.environ.get("HOROVOD_DRIVER_EPOCH", "0") or 0)
    except ValueError:
        spawn_epoch = 0
    try:
        timeout = float(os.environ.get("HOROVOD_ELASTIC_TIMEOUT", "600"))
    except ValueError:
        timeout = 600.0
    kv = KVStoreClient(addr, port)
    logger.warning(
        "elastic: spare %s parked at the spare gate (awaiting "
        "promotion)", wid,
    )
    beat = 0
    last_seen = time.monotonic()
    while True:
        world = driver = None
        promote_gen = None
        try:
            raw = kv.get("elastic", "world")
            world = json.loads(raw.decode()) if raw else None
            raw = kv.get("elastic", "driver")
            driver = json.loads(raw.decode()) if raw else None
            raw = kv.get("elastic", f"promote.{wid}")
            if raw:
                promote_gen = int(raw.decode())
        except Exception:  # noqa: BLE001 - driver briefly unreachable
            pass
        if driver is not None:
            last_seen = time.monotonic()
            try:
                epoch = int(driver.get("epoch", 0) or 0)
            except (TypeError, ValueError):
                epoch = 0
            if spawn_epoch and epoch > spawn_epoch:
                logger.warning(
                    "elastic: spare %s superseded (driver epoch %s > "
                    "spawn epoch %s); exiting — the resumed driver "
                    "spawns its own pool", wid, epoch, spawn_epoch,
                )
                sys.exit(0)
        elif time.monotonic() - last_seen > timeout:
            logger.warning(
                "elastic: spare %s saw no driver for %gs; exiting",
                wid, timeout,
            )
            sys.exit(0)
        assignments = (world or {}).get("assignments") or {}
        if (promote_gen is not None and wid in assignments
                and int((world or {}).get("gen", -1)) == promote_gen):
            a = assignments[wid]
            os.environ.update({
                "HOROVOD_RANK": str(a["rank"]),
                "HOROVOD_SIZE": str(world["size"]),
                "HOROVOD_LOCAL_RANK": str(a["local_rank"]),
                "HOROVOD_LOCAL_SIZE": str(a["local_size"]),
                "HOROVOD_CROSS_RANK": str(a["cross_rank"]),
                "HOROVOD_CROSS_SIZE": str(a["cross_size"]),
                "HOROVOD_CONTROLLER_ADDR": world["controller_addr"],
                "HOROVOD_CONTROLLER_PORT": str(world["controller_port"]),
                "HOROVOD_JAX_COORDINATOR": world["jax_coordinator"],
                "HOROVOD_ELASTIC_GEN": str(world["gen"]),
                "HOROVOD_ELASTIC_SYNC_ROOT": str(
                    world.get("sync_root", 0)
                ),
                "HOROVOD_DRIVER_EPOCH": str(
                    world.get("epoch", spawn_epoch)
                ),
            })
            os.environ.pop("HOROVOD_ELASTIC_SPARE", None)
            if _metrics.ACTIVE:
                _metrics.TAP.inc("hvd_spare_activations_total")
            if _fault_injector.ACTIVE:
                _fault_injector.record_event(
                    "driver", int(world["gen"]), "spare-adopt",
                    f"worker={wid}",
                )
            logger.warning(
                "elastic: spare %s promoted into generation %s as rank "
                "%s", wid, world["gen"], a["rank"],
            )
            return True
        beat += 1
        try:
            kv.put("elastic", f"spare.{wid}", str(beat).encode())
        except Exception:  # noqa: BLE001 - advisory heartbeat
            pass
        time.sleep(SPARE_POLL_S)


def apply_serve_scale(engine, decision):
    """Apply a serving autoscale verdict with the elastic verbs
    (docs/serving.md "Autoscale"): scale-out is the spare-promotion
    verb — a fresh DP serving replica joins the fleet — and scale-in
    the quarantine-shrink verb — the newest replica drains its current
    batch and retires. Event-logged through the fault injector's
    deterministic ledger like every other membership change, so a chaos
    diff sees serving resizes next to kills and promotions.

    Returns the replica index added/retired, or None when the engine
    refused (e.g. retiring the last replica)."""
    if decision is None:
        return None
    if decision.action == "scale-out":
        idx = engine.add_replica()
        verb = "serve-promote"
    else:
        idx = engine.retire_replica()
        verb = "serve-retire"
    if idx is None:
        return None
    if _fault_injector.ACTIVE:
        _fault_injector.record_event(
            "replica", idx, verb,
            f"reason={decision.reason} depth={decision.depth:.1f} "
            f"burn={decision.slo_burn:.3f}",
        )
    logger.warning(
        "elastic: serving %s replica %s (%s: depth=%.1f burn=%.3f)",
        "scale-out to" if verb == "serve-promote" else "scale-in of",
        idx, decision.reason, decision.depth, decision.slo_burn,
    )
    return idx


def _jax_distributed_initialize(coord: str, num: int, pid: int) -> None:
    """Stand up the JAX distributed runtime for an elastic world. Unlike
    ``jax.distributed.initialize``:

    - The coordination SERVICE is never created here — it lives in the
      elastic DRIVER process (one per world generation), so no worker is
      special: any worker, including generation rank 0, can crash without
      taking the coordination plane down with it (the reference's elastic
      driver owns the rendezvous for the same reason).
    - The client is failure-tolerant: ``recoverable=True`` (peer death is
      swallowed by the agent and surfaces as failed collectives, which the
      runtime turns into ``HorovodInternalError`` → rollback) and
      ``shutdown_on_destruction=False`` — OBJECT DESTRUCTION never issues
      the ShutdownTask RPC; the explicit, graceful
      ``_jax_distributed_teardown`` shuts the client down instead (safe
      because the driver-hosted service is alive to answer), which stops
      the error-poll/heartbeat threads before the channel dies under
      them. No ``missed_heartbeat_callback`` — the pybind functional
      bridge std::bad_cast-aborts when the agent's error-poll thread
      invokes a Python callback (jaxlib 0.9), and the driver-hosted
      service keeps heartbeats answerable for stragglers anyway."""
    from jax._src import distributed as _dist
    from jax._src.lib import _jax as _jaxlib

    state = _dist.global_state
    if state.client is not None:
        raise RuntimeError("jax distributed runtime is already initialized")
    init_timeout = int(float(
        os.environ.get("HOROVOD_ELASTIC_INIT_TIMEOUT", "120")
    ))
    heartbeat = int(float(
        os.environ.get("HOROVOD_ELASTIC_HEARTBEAT_S", "10")
    ))
    state.client = _jaxlib.get_distributed_runtime_client(
        coord, pid, init_timeout=init_timeout, use_compression=True,
        heartbeat_timeout=heartbeat,
        shutdown_on_destruction=False, recoverable=True,
    )
    logger.info("elastic: connecting to coordination service %s", coord)
    state.client.connect()
    state.process_id = pid
    state.num_processes = num
    state.coordinator_address = coord


def _jax_distributed_teardown() -> None:
    """Leave the current world. The client's background error-poll and
    heartbeat threads treat a dying channel as FATAL (client.h), so the
    client must be shut down gracefully BEFORE the object is dropped —
    safe here because the coordination service lives in the always-alive
    driver (a live endpoint to answer the ShutdownTask RPC) and the
    recoverable flag waives the shutdown barrier; a short-lived failure
    of that RPC is swallowed rather than escalated."""
    from jax._src import distributed as _dist

    state = _dist.global_state
    if state.preemption_sync_manager is not None:
        try:
            state.preemption_sync_manager.shutdown()
        except Exception:  # noqa: BLE001
            pass
        state.preemption_sync_manager = None
    if state.client is not None:
        try:
            state.client.shutdown()
        except Exception as exc:  # noqa: BLE001 - half-dead world
            logger.info("elastic: client shutdown reported %s", exc)
    state.client = None
    if state.service is not None:
        try:
            state.service.shutdown()
        except Exception:  # noqa: BLE001
            pass
        state.service = None


def _reset_jax_world() -> None:
    """Tear down the JAX distributed client and backend caches so the next
    ``hvd.init()`` can stand up a DIFFERENT world size in this process.
    (Validated: surviving processes of an N-world re-form an M-world and
    produce correct collectives after this reset.)"""
    import jax

    try:
        _jax_distributed_teardown()
    except Exception:  # noqa: BLE001 - not initialized / already gone
        pass
    try:
        jax.clear_caches()
    except Exception:  # noqa: BLE001
        pass
    try:
        from jax._src import xla_bridge as _xb

        _xb._clear_backends()
    except Exception as exc:  # noqa: BLE001 - jax internals moved
        logger.warning("could not clear XLA backends: %s", exc)


# ------------------------------------------------- rejoin-mode selection
# Exit status a worker uses to ask the driver for a fresh process instead
# of re-forming the world in-process. Must match REJOIN_EXIT_CODE in
# run/elastic_driver.py (kept as literals on both sides so the launcher
# never has to import this — jax-loading — module).
REJOIN_EXIT_CODE = 79

_rejoin_mode: Optional[str] = None


def _inprocess_rejoin_supported() -> bool:
    """In-process world re-formation rides three private JAX surfaces:
    the ``jax_enable_recoverability`` config flag (a dead peer surfaces
    on survivors as a catchable collective error, not a fatal
    coordination abort), ``xla_bridge._clear_backends`` (the next
    ``hvd.init()`` can stand up a different world size in this process),
    and the ``jax._src.lib._jax`` distributed-runtime factories (the
    recoverable client here, the driver-hosted coordination service in
    ``run/elastic_driver.py`` — older jaxlibs keep them under a
    different module name and without the ``recoverable`` kwarg). Any of
    these can vanish or move in a minor upgrade — probe them up front
    instead of finding out mid-crash-recovery."""
    try:
        import jax
        from jax._src import xla_bridge as _xb
        from jax._src.lib import _jax as _jaxlib
    except Exception:  # noqa: BLE001 - jax internals moved wholesale
        return False
    if not callable(getattr(_xb, "_clear_backends", None)):
        return False
    for factory in (
        "get_distributed_runtime_service", "get_distributed_runtime_client"
    ):
        if not callable(getattr(_jaxlib, factory, None)):
            return False
    try:
        # Attribute access raises if the flag no longer exists.
        jax.config.jax_enable_recoverability  # noqa: B018
    except Exception:  # noqa: BLE001
        return False
    return True


def rejoin_mode() -> str:
    """Active recovery mode: ``'inprocess'`` (generation-based world
    re-formation without process death — the fast path) or ``'respawn'``
    (the worker persists its last commit and exits with
    ``REJOIN_EXIT_CODE``; the driver respawns the slot and the fresh
    process resumes from the snapshot — upstream's restart semantics,
    used as the fallback when the private JAX surfaces the in-process
    path needs are absent). ``HOROVOD_ELASTIC_REJOIN_MODE`` forces
    either; the elastic driver resolves the mode once and exports it so
    every worker agrees."""
    global _rejoin_mode
    if _rejoin_mode is None:
        forced = os.environ.get(
            "HOROVOD_ELASTIC_REJOIN_MODE", "auto"
        ).lower()
        if forced == "inprocess" and not _inprocess_rejoin_supported():
            # Honoring the pin anyway would fatal-abort the first
            # crash recovery (the private JAX surfaces are absent);
            # degrade loudly instead.
            logger.warning(
                "elastic: HOROVOD_ELASTIC_REJOIN_MODE=inprocess but this "
                "jax lacks the required private surfaces; falling back "
                "to 'respawn'"
            )
            _rejoin_mode = "respawn"
        elif forced in ("inprocess", "respawn"):
            _rejoin_mode = forced
        else:
            _rejoin_mode = (
                "inprocess" if _inprocess_rejoin_supported() else "respawn"
            )
        logger.info("elastic: rejoin mode '%s'", _rejoin_mode)
    return _rejoin_mode


def _persist_path() -> Optional[str]:
    """Per-slot snapshot file in the driver-shared state dir. Keyed by
    worker id (host:local_rank), so a respawn of the same slot — on the
    same host, hence the same local filesystem — finds its predecessor's
    last commit."""
    d = os.environ.get("HOROVOD_ELASTIC_STATE_DIR")
    wid = os.environ.get("HOROVOD_ELASTIC_WORKER_ID")
    if not d or not wid:
        return None
    safe = wid.replace(":", "_").replace("/", "_")
    return os.path.join(d, f"{safe}.state.pkl")


def _persist_state_and_exit(state: "State", ctx: _ElasticContext) -> None:
    """Respawn-mode rejoin: snapshot the state to disk, signal the
    driver, and exit with the rejoin status. Never returns."""
    import pickle

    path = _persist_path()
    if path is not None:
        try:
            state.save()
            payload = _persist_payload(state)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                pickle.dump(payload, f)
            os.replace(tmp, path)
        except Exception as exc:  # noqa: BLE001 - degrade, don't hang
            logger.warning(
                "elastic: could not persist state (%s); the respawn will "
                "re-sync from a peer's snapshot instead", exc
            )
    else:
        logger.warning(
            "elastic: no HOROVOD_ELASTIC_STATE_DIR/WORKER_ID; respawn "
            "resumes from peers' snapshots only"
        )
    # The rejoin signal both tells the driver this generation is
    # abandoned and keeps its reconcile loop re-arming until a fresh
    # generation is actually published.
    ctx.signal_rejoin()
    logger.info(
        "elastic: exiting for respawn (status %d)", REJOIN_EXIT_CODE
    )
    # os._exit: the world is half-dead; a graceful interpreter shutdown
    # can hang joining runtime threads that are blocked on dead peers.
    os._exit(REJOIN_EXIT_CODE)


def _maybe_restore_persisted(state: "State") -> bool:
    """Respawn-mode startup: resume from this slot's persisted last
    commit, if any. Runs before the first sync so a restored snapshot is
    what a sync_root broadcasts (every rank's last commit is the same
    step — commits reach cross-rank agreement before returning).
    Returns True when a snapshot was restored."""
    import pickle

    path = _persist_path()
    if path is None or not os.path.exists(path):
        return False
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
    except Exception as exc:  # noqa: BLE001 - torn write, stale format
        # Quarantine the broken snapshot instead of warning and
        # re-reading the same bytes every generation: renamed aside it
        # can never be retried (or mistaken for live state by a later
        # respawn), while staying on disk for post-mortem.
        quarantined = f"{path}.corrupt"
        try:
            os.replace(path, quarantined)
            logger.error(
                "elastic: unreadable persisted state (%s); quarantined "
                "to %s — this slot resumes from a peer's snapshot",
                exc, quarantined,
            )
        except OSError as mv_exc:
            logger.warning(
                "elastic: unreadable persisted state (%s); could not "
                "quarantine it either (%s)", exc, mv_exc,
            )
        if _metrics.ACTIVE:
            _metrics.TAP.inc("hvd_elastic_snapshot_quarantined_total")
        return False
    # Layout preflight BEFORE any state is applied: a world-size change
    # between save and restore either reshards the snapshot's sharded
    # zero1 state here or fails with an error naming both layouts —
    # never the deep zero.py axis-size ValueError mid-step.
    payload = _preflight_snapshot_layout(state, payload, path)
    _apply_payload(state, payload)
    state.restore()
    logger.info("elastic: restored persisted state from %s", path)
    return True


def _warn_if_unrestored(restored_any: bool) -> None:
    """Respawn-mode data-loss guard (advisor finding): a restart at
    generation > 1 means a previous world made progress, so when NO rank
    restored a snapshot the job is silently starting over from step 0.
    Shout about it — or, with ``HOROVOD_ELASTIC_REQUIRE_SNAPSHOT`` set,
    fail the worker instead of losing data quietly."""
    if restored_any:
        return
    try:
        gen = int(os.environ.get("HOROVOD_ELASTIC_GEN", "1") or 1)
    except ValueError:
        gen = 1
    if gen <= 1:
        return  # a genuine from-scratch start
    msg = (
        f"elastic: restart generation {gen} found no restored snapshot "
        "on ANY rank — training resumes from step 0 and all progress "
        "since the last commit is LOST. Check that "
        "HOROVOD_ELASTIC_STATE_DIR survives respawns (shared or "
        "host-local persistent storage)."
    )
    if os.environ.get(
        "HOROVOD_ELASTIC_REQUIRE_SNAPSHOT", ""
    ).strip().lower() in ("1", "true", "yes", "on"):
        raise RuntimeError(
            msg + " Failing because HOROVOD_ELASTIC_REQUIRE_SNAPSHOT is "
            "set."
        )
    logger.error(msg)
    if _metrics.ACTIVE:
        _metrics.TAP.inc("hvd_elastic_unrestored_restarts_total")


def _elect_restored_sync_root(ctx: _ElasticContext, restored: bool) -> None:
    """Respawn-mode guard against silent progress loss: the driver picks
    a sync_root before workers spawn, so it cannot know which slots will
    actually find a snapshot (rank 0's host may be a fresh replacement,
    or its pickle may be torn). A tiny allgather of per-rank restored
    flags re-elects the sync source onto the first rank that DID restore
    — identical on every rank, so the broadcast stays consistent — and
    only keeps the driver's choice when nobody restored (a genuine
    from-scratch restart)."""
    import horovod_tpu as hvd

    if hvd.size() <= 1:
        _warn_if_unrestored(restored)
        return
    flags = hvd.allgather_object(bool(restored), name="hvd.elastic.snap")
    _warn_if_unrestored(any(flags))
    if not flags[ctx.sync_root] and any(flags):
        new_root = flags.index(True)
        logger.info(
            "elastic: sync root %d has no snapshot; re-electing rank %d "
            "(restored)", ctx.sync_root, new_root,
        )
        ctx.sync_root = new_root


def _persist_payload(state: "State") -> Dict[str, Any]:
    """Everything a ``save()`` produced, generically: every ``_saved*``
    attribute. ObjectState keeps the tracked dict in ``_saved``;
    subclasses add their own snapshot attrs (TorchState
    ``_saved_model``/``_saved_opt``, TensorFlowState ``_saved_vars``,
    TensorFlowKerasState ``_saved_weights``/``_saved_opt_vars``) — an
    allowlist here would silently drop any of them and a respawn would
    resume with reinitialized weights under a restored step counter.

    The snapshot is stamped with its world layout (``__layout__``: the
    saving world size plus any attached ZeRO-1 bucket layouts) so a
    restore at a DIFFERENT world size can preflight the mismatch and
    route sharded state through ``parallel/reshard`` instead of dying
    at the zero.py axis-size raise mid-step. Older readers ignore the
    key (``_apply_payload`` only consumes ``_saved*``)."""
    payload = {
        k: v for k, v in vars(state).items() if k.startswith("_saved")
    }
    payload["__layout__"] = _snapshot_layout_stamp(state)
    return payload


def _zero1_shard_dims(payload: Dict[str, Any]) -> Dict[str, int]:
    """``{payload_key/tree_path: leading shard count}`` for every
    Zero1State found inside the ``_saved*`` snapshot values."""
    try:
        from ..parallel.zero import Zero1State
    except Exception:  # noqa: BLE001 - jax-free install
        return {}

    dims: Dict[str, int] = {}

    def scan(prefix: str, node: Any) -> None:
        if isinstance(node, Zero1State):
            for leaf in _tree_leaves(node.opt):
                shape = getattr(leaf, "shape", ())
                if len(shape) >= 1:
                    dims[prefix] = int(shape[0])
                    return
            dims[prefix] = 0
            return
        if isinstance(node, dict):
            for k, v in node.items():
                scan(f"{prefix}/{k}" if prefix else str(k), v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                scan(f"{prefix}/{i}" if prefix else str(i), v)

    for key, value in payload.items():
        if key.startswith("_saved"):
            scan(key, value)
    return dims


def _tree_leaves(node: Any):
    import jax

    return jax.tree.leaves(node)


def _snapshot_layout_stamp(state: "State") -> Dict[str, Any]:
    try:
        world = int(os.environ.get("HOROVOD_SIZE", "1") or 1)
    except ValueError:
        world = 1
    layouts = getattr(state, "zero1_layout", None) or {}
    serialized = {}
    for attr, lay in dict(layouts).items():
        serialized[str(attr)] = (
            lay.to_dict() if hasattr(lay, "to_dict") else dict(lay)
        )
    return {"world": world, "zero1_layout": serialized}


def note_zero1_layout(state: "State", attr: str, layout: Any) -> None:
    """Attach the ZeRO-1 bucket layout of tracked attribute ``attr``
    (from ``parallel/reshard.zero1_layout_from_params``) to ``state`` so
    elastic snapshots and in-process resizes can reshard it across a
    world-shape change. Without a layout, a resize with sharded state
    refuses loudly instead of silently corrupting shard offsets."""
    layouts = getattr(state, "zero1_layout", None)
    if layouts is None:
        layouts = {}
        state.zero1_layout = layouts
    layouts[str(attr)] = layout


def _preflight_snapshot_layout(state: "State",
                               payload: Dict[str, Any],
                               path: str) -> Dict[str, Any]:
    """Respawn-mode layout preflight: a snapshot persisted at one world
    size restoring into a DIFFERENT one used to surface as a deep
    ``zero.py`` ValueError ("optimizer state is sharded N ways...") on
    the first post-restore step. Instead: compare the snapshot's
    recorded layout against the new generation here, reshard every
    Zero1State through ``parallel/reshard`` when a bucket layout is
    available, and otherwise raise an error naming BOTH layouts."""
    dims = _zero1_shard_dims(payload)
    stamp = payload.get("__layout__") or {}
    snap_world = stamp.get("world")
    try:
        cur = int(os.environ.get("HOROVOD_SIZE", "1") or 1)
    except ValueError:
        cur = 1
    if not dims:
        return payload  # replicated snapshot: any world size fits
    mismatched = {k: n for k, n in dims.items() if n != cur}
    if not mismatched:
        return payload
    layouts = dict(stamp.get("zero1_layout") or {})
    if not layouts:
        raise RuntimeError(
            f"elastic: snapshot {path} holds ZeRO-1 state sharded for "
            f"a different world: snapshot layout (world="
            f"{snap_world if snap_world is not None else '?'}, shards "
            f"{dims}) vs new generation layout (world={cur}) — and no "
            f"bucket layout was recorded to reshard it. Attach one with "
            f"hvd.elastic.note_zero1_layout(state, attr, "
            f"zero1_layout_from_params(...)) before the first commit, "
            f"or restore from a sharded checkpoint "
            f"(docs/fault_tolerance.md 'Elastic resharding')."
        )
    from ..parallel import reshard as _reshard

    out = dict(payload)
    for key in list(out):
        if not key.startswith("_saved"):
            continue
        value = out[key]
        if not isinstance(value, dict):
            continue
        new_value = dict(value)
        for attr, sub in value.items():
            attr_dims = _zero1_shard_dims({"_saved": {attr: sub}})
            if not attr_dims:
                continue
            lay = layouts.get(str(attr))
            if lay is None:
                raise RuntimeError(
                    f"elastic: snapshot {path} attr {attr!r} holds "
                    f"ZeRO-1 state sharded {sorted(set(attr_dims.values()))}"
                    f" ways (snapshot world="
                    f"{snap_world if snap_world is not None else '?'}) "
                    f"but the new generation has world={cur} and no "
                    f"bucket layout was recorded for {attr!r} "
                    f"(known: {sorted(layouts)}) — attach one with "
                    f"hvd.elastic.note_zero1_layout."
                )
            resharded, reports = _reshard.reshard_zero1_tree(
                sub, cur, layouts={"": lay}, trigger="snapshot-restore",
            )
            new_value[attr] = resharded
            for rep in reports:
                logger.info(
                    "elastic: resharded snapshot attr %r zero1 state "
                    "%d->%d shards (%d bytes)", attr, rep["n_old"],
                    rep["n_new"], rep["moved_bytes"],
                )
        out[key] = new_value
    # Re-stamp for the world we just resharded into.
    new_layouts = {
        a: _reshard.Zero1Layout.from_dict(l).relayout(cur).to_dict()
        for a, l in layouts.items()
    }
    out["__layout__"] = {"world": cur, "zero1_layout": new_layouts}
    state.zero1_layout = {
        a: _reshard.Zero1Layout.from_dict(l) for a, l in new_layouts.items()
    }
    return out


def _apply_payload(state: "State", payload: Dict[str, Any]) -> None:
    if "tracked" in payload and "_saved" not in payload:
        payload = dict(payload)
        payload["_saved"] = payload.pop("tracked")  # pre-r5 layout
    for k, v in payload.items():
        if k.startswith("_saved"):
            setattr(state, k, v)


def _clear_persisted() -> None:
    path = _persist_path()
    if path is not None and os.path.exists(path):
        try:
            os.remove(path)
        except OSError:
            pass


def _rejoin(ctx: _ElasticContext) -> None:
    """Leave the current (broken or stale) world and join the next
    generation: wait for the driver to publish gen > current with this
    worker in it, then re-init. A worker dropped from the new world exits
    cleanly (the driver also terminates it as a backstop)."""
    import horovod_tpu as hvd

    ctx.signal_rejoin()
    try:
        hvd.shutdown()
    except Exception:  # noqa: BLE001 - already torn down
        pass
    _reset_jax_world()
    deadline = time.monotonic() + ctx.timeout
    while True:
        if time.monotonic() > deadline:
            raise RuntimeError(
                "elastic: no usable world generation within "
                f"{ctx.timeout}s (last known gen {ctx.gen})"
            )
        world = None
        try:
            world = ctx.fetch_world()
        except Exception:  # noqa: BLE001 - driver briefly unreachable
            pass
        if not world or int(world["gen"]) <= ctx.gen:
            time.sleep(0.2)
            continue
        if not ctx.apply(world):
            # Scaled down past this worker: graceful departure.
            logger.info(
                "elastic: worker %s not in generation %s; exiting",
                ctx.worker_id, world["gen"],
            )
            sys.exit(0)
        try:
            hvd.init()
            ctx.gen = int(world["gen"])  # committed only on success
            if _trace.ACTIVE:
                # Ranks are renumbered in the new generation: restart
                # the step ledger so the driver's skew attribution never
                # compares step indices across a resize (a removed rank
                # must not be charged for a stranger's steps).
                _trace.TAP.reset_steps()
            # A re-plan notice is generation-scoped; whatever was
            # pending died with the old world.
            ctx._pending_replan = None
            # A resumed driver supervising adopted workers has no
            # process handle on this rank: the attach signal (stamped
            # with the generation + acknowledged epoch) is how it learns
            # the rejoin landed.
            ctx.signal_attach()
            if _metrics.ACTIVE:
                _metrics.TAP.inc("hvd_elastic_rejoins_total")
            return
        except Exception as exc:  # noqa: BLE001 - racing another bump
            logger.warning(
                "elastic: init at gen %s failed (%s); retrying",
                world["gen"], exc,
            )
            try:
                hvd.shutdown()
            except Exception:  # noqa: BLE001
                pass
            _reset_jax_world()
            time.sleep(0.5)


_sync_root_override: Optional[int] = None


def _sync_root() -> int:
    """Rank whose state is authoritative for the current generation: a
    survivor of the previous world (published by the driver), so a fresh
    respawn that happened to land on rank 0 can never broadcast its
    just-constructed state over everyone's progress. The digest guard's
    heal path overrides it transiently (``_sync_root_as``) to
    re-broadcast from the agreeing quorum's reference rank."""
    if _sync_root_override is not None:
        return _sync_root_override
    ctx = _ctx()
    return ctx.sync_root if ctx is not None else 0


@contextlib.contextmanager
def _sync_root_as(root: int):
    """Temporarily force the sync root (digest-guard healing): every rank
    enters this context with the SAME root, so the broadcasts stay
    collective."""
    global _sync_root_override
    prev = _sync_root_override
    _sync_root_override = int(root)
    try:
        yield
    finally:
        _sync_root_override = prev


# ----------------------------------------------------------------- state
class State:
    """Base class for elastic state (upstream ``horovod.elastic.State``):
    ``commit()`` snapshots + checks for membership changes,
    ``restore()`` rolls back to the last commit, ``sync()`` aligns all
    ranks to rank 0's state after a re-rendezvous."""

    def __init__(self) -> None:
        self._reset_callbacks: List[Callable[[], None]] = []
        # Commit counter for the parameter-digest guard
        # (HOROVOD_GUARD_DIGEST_STEPS; docs/fault_tolerance.md).
        self._guard_commits = 0

    def register_reset_callbacks(
        self, callbacks: List[Callable[[], None]]
    ) -> None:
        """Callbacks invoked after each world re-formation (learning-rate
        rescale, dataset re-partition, ...)."""
        self._reset_callbacks.extend(callbacks)

    def on_reset(self) -> None:
        for cb in self._reset_callbacks:
            cb()

    def commit(self) -> None:
        if _trace.ACTIVE:
            # Fleet-tracing step boundary (docs/timeline.md "Step
            # spans"): one commit == one training step for loops that
            # commit per step, so the inter-commit window doubles as the
            # step span feeding the driver's skew attribution — unless a
            # wrap_step tap already records real step spans.
            _trace.TAP.commit_step()
        if _fault_injector.ACTIVE:
            # Chaos tap: one commit == one training step; kill/preempt
            # actions with at_step target this counter.
            _fault_injector.fault_point("step")
        if _guard.ACTIVE:
            # Digest agreement BEFORE save(): a silently diverged replica
            # must never become the rollback point. Heals in place (the
            # heal's sync() snapshots) or raises for the elastic rollback.
            self._guard_check_digest()
        self.save()
        self.check_host_updates()

    def _guard_check_digest(self) -> None:
        """Periodic cross-rank parameter-digest agreement
        (docs/fault_tolerance.md "Data-plane integrity"): every
        ``HOROVOD_GUARD_DIGEST_STEPS`` commits, hash the tracked state,
        allgather the digests (bytes, not payloads), and on mismatch
        self-heal — re-broadcast from the agreeing quorum's reference
        rank, or roll back to the last commit when no quorum exists."""
        steps = _guard.digest_steps()
        if steps <= 0:
            return
        self._guard_commits += 1
        if self._guard_commits % steps:
            return
        import horovod_tpu as hvd

        if not hvd.is_initialized() or hvd.size() <= 1:
            return
        from ..guard import digest as _digest

        mine = _digest.state_digest(self)
        digests = hvd.allgather_object(mine, name="hvd.guard.digest")
        if _metrics.ACTIVE:
            _metrics.TAP.inc("hvd_guard_digest_checks_total")
        ok, ref, outliers = _digest.find_quorum(
            digests,
            no_quorum=_guard.no_quorum_action(),
            sync_root=_sync_root(),
        )
        if ok:
            return
        if _metrics.ACTIVE:
            _metrics.TAP.inc("hvd_guard_digest_mismatches_total")
        rt = getattr(hvd, "_runtime", None)
        tl = getattr(rt, "timeline", None)
        if tl is not None and getattr(tl, "initialized", False):
            tl.metadata(
                "hvd_guard_digest_mismatch",
                {"outliers": outliers, "reference": ref},
            )
        if ref is None:
            _guard.record_guard_event(
                "digest-rollback", f"outliers={outliers}"
            )
            if _metrics.ACTIVE:
                _metrics.TAP.inc("hvd_guard_rollbacks_total")
            raise hvd.HorovodInternalError(
                "parameter digest mismatch across ranks "
                f"{outliers} with no agreeing quorum "
                "(HOROVOD_GUARD_DIGEST_STEPS guard); rolling back to the "
                "last commit"
            )
        _guard.record_guard_event(
            "digest-heal", f"ref={ref} outliers={outliers}"
        )
        logger.error(
            "digest guard: ranks %s diverged from the quorum; healing by "
            "re-broadcast from rank %d", outliers, ref,
        )
        # Heal: every rank (agreeing and diverged alike) re-syncs from
        # the reference — the broadcasts are collective. sync() also
        # save()s, so the healed state becomes the new rollback point.
        with _sync_root_as(ref):
            self.sync()
        if _metrics.ACTIVE:
            _metrics.TAP.inc("hvd_guard_heals_total")

    # Per-rank vote bits for the commit-time agreement allreduce: each
    # rank ORs its local observations into one int32 and the fleet
    # agrees with op=Max (same idiom as the park-outcome agreement).
    # The decision ladder only acts on the strongest signal present, so
    # Max losing weaker bits is harmless — and unlike a weighted Sum the
    # scheme is rank-count independent (no overflow band to outgrow).
    # Ordered by severity: a pending re-plan is the WEAKEST signal (a
    # membership change, preemption, or driver loss each makes the
    # notice moot — the next generation re-plans on fresh evidence).
    _REPLAN_BIT = 1
    _UPDATED_BIT = 2
    _PREEMPT_BIT = 4
    _LOST_BIT = 8

    def check_host_updates(self) -> None:
        """Raise ``HostsUpdatedInterrupt`` on EVERY rank when any rank has
        seen a newer world generation — agreement by allreduce so no rank
        runs ahead into a collective its peers abandoned. A pending
        preemption notice rides the same agreement: the preempted rank
        raises ``PreemptionInterrupt`` (drain + rejoin with the state just
        committed), its peers a plain membership interrupt.

        The same probe doubles as the driver heartbeat/epoch check
        (docs/fault_tolerance.md "Control-plane availability"): when any
        rank has lost the driver, ALL ranks park at this commit boundary
        (state held, collectives quiesced) and reconnect/reattach; a
        driver that restarted without ever dropping off (epoch advanced,
        same generation) is reattached in place — a purely local act."""
        ctx = _ctx()
        if ctx is None:
            return
        import numpy as np

        import horovod_tpu as hvd

        preempted = _preemption.preemption_requested()
        updated, lost, new_epoch = ctx.commit_probe()
        replan = ctx.check_replan()
        if new_epoch is not None and not (lost or updated or preempted):
            ctx.reattach(new_epoch)
        flag = np.asarray(
            [(self._LOST_BIT if lost else 0)
             | (self._PREEMPT_BIT if preempted else 0)
             | (self._UPDATED_BIT if updated else 0)
             | (self._REPLAN_BIT if replan else 0)],
            np.int32,
        )
        if hvd.size() > 1:
            flag = np.asarray(
                hvd.allreduce(flag, op=hvd.Max, name="hvd.elastic.hostcheck")
            )
        agreed = int(flag[0])
        if preempted:
            raise PreemptionInterrupt(
                _preemption.preemption_reason() or "preemption notice"
            )
        if agreed >= self._LOST_BIT:
            _park_and_reattach(ctx, self)
            return
        if agreed >= self._PREEMPT_BIT:
            raise HostsUpdatedInterrupt(
                "a peer rank received a preemption notice; re-forming "
                "the world"
            )
        if agreed >= self._UPDATED_BIT:
            raise HostsUpdatedInterrupt(
                "host membership changed; re-forming the world"
            )
        if agreed >= self._REPLAN_BIT:
            _adopt_replan(ctx)

    # subclass responsibilities
    def save(self) -> None:
        raise NotImplementedError

    def restore(self) -> None:
        raise NotImplementedError

    def sync(self) -> None:
        raise NotImplementedError


class ObjectState(State):
    """State over arbitrary picklable attributes
    (``ObjectState(batch=0, epoch=0)``); sync ships rank 0's values with
    the object-allgather wire."""

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._tracked = sorted(kwargs)
        for k, v in kwargs.items():
            setattr(self, k, v)
        # Snapshot through the subclass's save() (JaxState needs its
        # device_get host copies, not deepcopied device arrays): a
        # rollback can happen before the first commit — e.g. a peer dies
        # during the initial sync — and restore() must already hold
        # backend-independent state.
        self._saved: Dict[str, Any] = {}
        self.save()

    def save(self) -> None:
        self._saved = {
            k: copy.deepcopy(getattr(self, k)) for k in self._tracked
        }

    def restore(self) -> None:
        for k, v in self._saved.items():
            self._assign(k, copy.deepcopy(v))

    def _assign(self, key: str, new: Any) -> None:
        """Bind ``new`` as the value of tracked attribute ``key``,
        mutating the existing object IN PLACE when it is a mutable
        container or a plain instance of the same class.

        External references must stay valid across rollbacks and
        re-formations: the documented ``DataLoader(sampler=sampler)``
        pattern (torch/elastic.py) holds the sampler object directly, so
        rebinding the attribute to a freshly-unpickled copy would leave
        the loader iterating stale state while commits snapshot the new
        object. The upstream reference mutates samplers in place via its
        state handlers for exactly this reason
        (ref: horovod/common/elastic.py state-handler design).

        ``new`` is always a throwaway (an unpickled wire copy or a
        deepcopy of a snapshot), so adopting its internals is safe.
        """
        cur = getattr(self, key, None)
        if cur is new:
            return
        if cur is not None and type(cur) is type(new):
            if isinstance(cur, dict):
                cur.clear()
                cur.update(new)
                return
            if isinstance(cur, list):
                cur[:] = new
                return
            if isinstance(cur, set):
                cur.clear()
                cur.update(new)
                return
            d_cur = getattr(cur, "__dict__", None)
            d_new = getattr(new, "__dict__", None)
            if isinstance(d_cur, dict) and isinstance(d_new, dict):
                d_cur.clear()
                d_cur.update(d_new)
                return
        setattr(self, key, new)

    @staticmethod
    def _is_sampler(v: Any) -> bool:
        # Duck-typed ElasticSampler (torch/elastic.py) — its processed
        # set is PER-RANK state that must union across ranks, not be
        # overwritten by the sync source's copy.
        return hasattr(v, "processed") and hasattr(v, "record_batch")

    def sync(self) -> None:
        import horovod_tpu as hvd

        if hvd.size() > 1:
            sampler_keys = [
                k for k in self._tracked
                if self._is_sampler(getattr(self, k))
            ]
            # Capture every rank's processed indices BEFORE the broadcast
            # overwrites the samplers (upstream's SamplerStateHandler
            # unions the same way): each rank trained a disjoint shard,
            # so resume-without-repeat needs the union.
            merged = (
                hvd.allgather_object(
                    {k: sorted(getattr(self, k).processed)
                     for k in sampler_keys},
                    name="hvd.elastic.sampsync",
                )
                if sampler_keys else []
            )
            values = {k: getattr(self, k) for k in self._tracked}
            synced = hvd.broadcast_object(
                values, root_rank=_sync_root(),
                name="hvd.elastic.objsync",
            )
            for k, v in synced.items():
                self._assign(k, v)
            for k in sampler_keys:
                s = getattr(self, k)
                s.processed = set().union(
                    *[set(m[k]) for m in merged]
                )
                s._local_order = []
        self.save()


def _broadcast_skipping_rank_local(hvd, tree: Any, root: int) -> Any:
    """Broadcast an array pytree from ``root`` WITHOUT clobbering
    rank-local nodes: Zero1State shard rows and EF residuals are
    distinct per rank by construction (the same leaves
    ``guard/digest.strip_rank_local`` excludes from cross-rank
    agreement), so a whole-tree broadcast would overwrite every rank's
    shards with the root's. Replicated leaves broadcast as before; an
    EFState's ``inner`` (cross-rank optimizer state) still syncs, only
    its ``residual`` stays local."""
    import jax

    try:
        from ..ops.quantized import EFState
        from ..parallel.zero import Zero1State
    except Exception:  # noqa: BLE001 - partial install
        return hvd.broadcast_variables(tree, root_rank=root)

    def is_rank_local(n: Any) -> bool:
        return isinstance(n, (Zero1State, EFState))

    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_rank_local)
    if not any(is_rank_local(l) for l in leaves):
        return hvd.broadcast_variables(tree, root_rank=root)
    plain_idx = [i for i, l in enumerate(leaves) if not is_rank_local(l)]
    if plain_idx:
        synced = hvd.broadcast_variables(
            [leaves[i] for i in plain_idx], root_rank=root
        )
        for i, v in zip(plain_idx, synced):
            leaves[i] = v
    for i, l in enumerate(leaves):
        if isinstance(l, EFState) and l.inner is not None:
            leaves[i] = EFState(
                inner=_broadcast_skipping_rank_local(hvd, l.inner, root),
                residual=l.residual,
            )
    return jax.tree.unflatten(treedef, leaves)


class JaxState(ObjectState):
    """State whose attributes are JAX pytrees (params, opt_state, plus
    plain counters). Array-leaf pytrees sync with fused tensor broadcasts
    (``broadcast_variables``); everything else rides the object wire.
    Saves are host-side snapshots (``jax.device_get``) so a rollback
    survives device-state teardown across generations."""

    def save(self) -> None:
        import jax

        self._saved = {
            k: jax.device_get(getattr(self, k)) for k in self._tracked
        }

    def sync(self) -> None:
        import jax

        import horovod_tpu as hvd

        if hvd.size() > 1:
            arrays = {}
            objects = {}
            for k in self._tracked:
                v = getattr(self, k)
                leaves = jax.tree.leaves(v)
                if leaves and all(hasattr(l, "shape") for l in leaves):
                    arrays[k] = v
                else:
                    # Plain counters / mixed pytrees ride the object wire.
                    objects[k] = v
            root = _sync_root()
            for k in sorted(arrays):
                setattr(
                    self, k,
                    _broadcast_skipping_rank_local(
                        hvd, arrays[k], root
                    ),
                )
            if objects:
                synced = hvd.broadcast_object(
                    objects, root_rank=root, name="hvd.elastic.objsync"
                )
                for k, v in synced.items():
                    self._assign(k, v)
        self.save()


class TorchState(ObjectState):
    """State over a torch model + optimizer (plus plain counters):
    upstream ``horovod.torch.elastic.TorchState`` role. save/restore use
    ``state_dict`` deep copies; sync broadcasts rank 0's parameters and
    optimizer state with the existing torch binding."""

    def __init__(self, model=None, optimizer=None, **kwargs: Any) -> None:
        self.model = model
        self.optimizer = optimizer
        # ObjectState.__init__ takes the initial snapshot via save().
        super().__init__(**kwargs)

    def save(self) -> None:
        super().save()
        if self.model is not None:
            self._saved_model = copy.deepcopy(self.model.state_dict())
        if self.optimizer is not None:
            self._saved_opt = copy.deepcopy(self.optimizer.state_dict())

    def _reset_optimizer_handles(self) -> None:
        # A DistributedOptimizer's in-flight allreduce handles reference
        # a dead world after a rollback; a failure raised OUTSIDE its own
        # synchronize() (e.g. a logging allreduce between backward and
        # step) leaves them set, and the next zero_grad() would refuse.
        reset = getattr(self.optimizer, "reset", None)
        if callable(reset):
            reset()

    def restore(self) -> None:
        super().restore()
        self._reset_optimizer_handles()
        if self.model is not None:
            self.model.load_state_dict(self._saved_model)
        if self.optimizer is not None:
            self.optimizer.load_state_dict(self._saved_opt)

    def sync(self) -> None:
        import horovod_tpu as hvd

        self._reset_optimizer_handles()
        if hvd.size() > 1:
            import horovod_tpu.torch as hvd_torch

            root = _sync_root()
            if self.model is not None:
                hvd_torch.broadcast_parameters(
                    self.model.state_dict(), root_rank=root
                )
            if self.optimizer is not None:
                hvd_torch.broadcast_optimizer_state(
                    self.optimizer, root_rank=root
                )
        super().sync()


class TensorFlowState(ObjectState):
    """State over raw ``tf.Variable`` collections (upstream
    ``horovod.tensorflow.elastic.TensorFlowState`` role): pass the
    variables plus plain counters. ``variables`` may be a CALLABLE
    (e.g. ``lambda: model.trainable_variables``) so lazily built
    variables are picked up at every save/restore/sync; a plain list is
    frozen at construction — commit after the model is built, or a
    count mismatch is warned about and the optimizer-style half-restore
    skipped. sync broadcasts the sync root's values through the TF
    binding's ``broadcast_variables``."""

    def __init__(self, variables=None, **kwargs: Any) -> None:
        self.variables = (
            variables if callable(variables)
            else list(variables) if variables is not None else []
        )
        super().__init__(**kwargs)

    def _vars(self) -> list:
        return list(self.variables() if callable(self.variables)
                    else self.variables)

    def save(self) -> None:
        super().save()
        import numpy as np

        self._saved_vars = [np.array(v) for v in self._vars()]

    def restore(self) -> None:
        cur = self._vars()
        if len(cur) != len(self._saved_vars):
            # Nothing is rolled back — counters included: a half-restore
            # (old counters, new weights) would silently re-apply
            # training on already-trained weights if this rank becomes
            # the sync root.
            logger.warning(
                "elastic: variable count changed since the last snapshot "
                "(%d saved vs %d now); NOTHING was rolled back — "
                "commit() after the model is built, or pass a callable "
                "so new variables are tracked",
                len(self._saved_vars), len(cur),
            )
            return
        super().restore()
        for var, val in zip(cur, self._saved_vars):
            var.assign(val)

    def sync(self) -> None:
        import horovod_tpu as hvd

        cur = self._vars()
        if hvd.size() > 1 and cur:
            from ..tensorflow import broadcast_variables as _tf_bcast

            _tf_bcast(cur, root_rank=_sync_root())
        super().sync()


class TensorFlowKerasState(ObjectState):
    """State over a Keras model (plus plain counters): upstream
    ``horovod.elastic.TensorFlowKerasState`` role. save/restore use
    weight-array copies; sync broadcasts rank 0's weights (and the
    optimizer's variables when it exposes any) with the numpy wire."""

    def __init__(self, model, optimizer=None, **kwargs: Any) -> None:
        self.model = model
        self.optimizer = optimizer or getattr(model, "optimizer", None)
        # ObjectState.__init__ takes the initial snapshot via save().
        super().__init__(**kwargs)

    @staticmethod
    def _opt_vars(optimizer):
        # Keras 3 exposes .variables; tf-keras 2 .weights.
        for attr in ("variables", "weights"):
            v = getattr(optimizer, attr, None)
            if v:
                return list(v)
        return []

    def save(self) -> None:
        super().save()
        import numpy as np

        self._saved_weights = [
            np.array(w) for w in self.model.get_weights()
        ]
        if self.optimizer is not None:
            self._saved_opt_vars = [
                np.array(v) for v in self._opt_vars(self.optimizer)
            ]

    def restore(self) -> None:
        super().restore()
        self.model.set_weights(self._saved_weights)
        if self.optimizer is not None:
            ovars = self._opt_vars(self.optimizer)
            if len(ovars) == len(self._saved_opt_vars):
                for var, val in zip(ovars, self._saved_opt_vars):
                    var.assign(val)
            else:
                # Keras builds slot variables lazily; a snapshot taken
                # before the first apply cannot restore them. The weights
                # ARE rolled back — warn that momentum/iteration state is
                # not, instead of silently half-restoring.
                logger.warning(
                    "elastic: optimizer variable count changed since the "
                    "last snapshot (%d saved vs %d now); optimizer state "
                    "was NOT rolled back — commit() after the first "
                    "optimizer step to make it restorable",
                    len(self._saved_opt_vars), len(ovars),
                )

    def sync(self) -> None:
        import numpy as np

        import horovod_tpu as hvd

        if hvd.size() > 1:
            root = _sync_root()
            synced = hvd.broadcast_variables(
                [np.asarray(w) for w in self.model.get_weights()],
                root_rank=root,
            )
            self.model.set_weights([np.asarray(w) for w in synced])
            if self.optimizer is not None:
                ovars = self._opt_vars(self.optimizer)
                if ovars:
                    vals = hvd.broadcast_variables(
                        [np.asarray(v) for v in ovars], root_rank=root
                    )
                    for var, val in zip(ovars, vals):
                        var.assign(np.asarray(val))
        super().sync()


def _is_collective_failure(exc: BaseException) -> bool:
    """True when ``exc`` is (or wraps) a failed collective. Framework
    runtimes re-raise our op failures under their own exception types —
    a TF async op kernel fails a ``tf.function`` step with
    ``tf.errors.InternalError`` carrying the collective's message — so
    the elastic wrapper matches on origin + message, not only on
    ``HorovodInternalError`` (upstream's TF elastic does the same)."""
    import horovod_tpu as hvd

    if isinstance(exc, hvd.HorovodInternalError):
        return True
    if type(exc).__module__.partition(".")[0] == "tensorflow":
        # Only failures our own runtime emits into failed op kernels — a
        # deterministic user error inside a horovod-named op (shape
        # mismatch, unregistered op) must SURFACE, not spin the rollback
        # loop forever. Every graph-op failure carries the stable
        # [hvd-collective-failure] prefix (graph_ops.finish_error); the
        # remaining substrings cover enqueue-time raises that reach TF
        # before an op kernel exists.
        msg = str(exc)
        return ("[hvd-collective-failure]" in msg
                or "Horovod control plane" in msg
                or "Horovod has been shut down" in msg
                or "lost a peer rank" in msg
                or "lost the coordinator" in msg
                # Enqueue raced the teardown of a dying world:
                or "core is not running" in msg
                or "Horovod runtime is shut down" in msg)
    return False


# ------------------------------------------------------------------- run
def run(func: Callable) -> Callable:
    """Decorator making ``func(state, *args)`` elastic (upstream
    ``hvd.elastic.run``). On ``HorovodInternalError`` (peer failure) the
    state rolls back to the last commit; on ``HostsUpdatedInterrupt``
    (graceful membership change) it is kept. Either way the worker
    re-rendezvouses with the next world generation, re-syncs from the new
    rank 0, fires reset callbacks, and re-enters ``func``.

    Outside an elastic launch (no ``--host-discovery-script``/``--min-np``)
    the wrapper is a plain call."""

    @functools.wraps(func)
    def wrapper(state: State, *args: Any, **kwargs: Any) -> Any:
        import horovod_tpu as hvd

        ctx = _ctx()
        if ctx is None:
            return func(state, *args, **kwargs)
        mode = rejoin_mode()
        if os.environ.get(
            "HOROVOD_PREEMPTION_GRACEFUL", "1"
        ).strip().lower() not in ("0", "false", "no", "off"):
            # SIGTERM is the platform's maintenance/preemption notice:
            # turn it into a graceful drain (commit → drain → rejoin)
            # instead of an instant death. The driver's SIGKILL escalation
            # still bounds a worker that never reaches another commit.
            _preemption.install_sigterm_handler()
        if mode == "respawn":
            restored = _maybe_restore_persisted(state)
            _elect_restored_sync_root(ctx, restored)
        while True:
            try:
                state.sync()
                # From here this worker holds live state: eligible as a
                # future generation's sync source.
                ctx.confirm_joined()
                result = func(state, *args, **kwargs)
                # A resumed (adopting) driver cannot see this process
                # exit; the done signal is its completion record.
                ctx.signal_done()
                if mode == "respawn":
                    # Clean finish: a leftover snapshot must not
                    # resurrect into an unrelated later job on this slot.
                    _clear_persisted()
                return result
            except PlanUpdatedInterrupt as exc:
                # A live re-plan is NOT a membership change: the world
                # (and the committed state) is intact, so no rollback,
                # no re-rendezvous, no reset callbacks — re-enter the
                # training function so it rebuilds its step from
                # adopted_step_kwargs(). The loop-top sync() keeps the
                # re-entry collective (every rank adopted at the same
                # commit boundary).
                logger.warning(
                    "elastic: %s; re-entering the training function", exc
                )
                continue
            except HostsUpdatedInterrupt:
                if _metrics.ACTIVE:
                    _metrics.TAP.inc("hvd_elastic_host_interrupts_total")
                logger.info(
                    "elastic: membership change; rejoining with current "
                    "state"
                )
            except PreemptionInterrupt as exc:
                # The notice was observed inside commit(): the state is
                # already saved. Keep it (no rollback), drain the
                # in-flight collectives with the runtime teardown below
                # (_persist_state_and_exit / _rejoin both shut the
                # runtime down), and rejoin through the elastic path.
                if _metrics.ACTIVE:
                    _metrics.TAP.inc("hvd_elastic_preemptions_total")
                logger.warning(
                    "elastic: preemption notice (%s); draining and "
                    "rejoining with the just-committed state", exc,
                )
                _preemption.clear()
            except Exception as exc:  # noqa: BLE001 - filtered below
                if not _is_collective_failure(exc):
                    raise
                if _metrics.ACTIVE:
                    _metrics.TAP.inc("hvd_elastic_rollbacks_total")
                logger.warning(
                    "elastic: collective failure (%s); rolling back to the "
                    "last commit and rejoining", exc,
                )
                state.restore()
            if mode == "respawn":
                _persist_state_and_exit(state, ctx)  # never returns
            try:
                old_size = int(os.environ.get("HOROVOD_SIZE", "1") or 1)
            except ValueError:
                old_size = 1
            _rejoin(ctx)
            try:
                new_size = int(os.environ.get("HOROVOD_SIZE", "1") or 1)
            except ValueError:
                new_size = 1
            if new_size != old_size:
                _reshard_state_for_world(state, old_size, new_size)
            state.on_reset()

    return wrapper


def _reshard_state_for_world(state: State, old_size: int,
                             new_size: int) -> None:
    """In-process resize (quarantine shrink, spare-promotion grow,
    scale-in/out): re-stack every tracked Zero1State attribute — and its
    host snapshot — onto the new world size via ``parallel/reshard``,
    instead of letting the first post-resize step die at the zero.py
    axis-size raise. Needs the bucket layouts attached via
    :func:`note_zero1_layout`; sharded state without one refuses loudly
    naming both layouts."""
    try:
        from ..parallel.zero import Zero1State  # noqa: F401 - probe
    except Exception:  # noqa: BLE001 - jax-free install: nothing sharded
        return

    tracked = list(getattr(state, "_tracked", []))
    sharded = []
    for attr in tracked:
        dims = _zero1_shard_dims({"_saved": {attr: getattr(state, attr)}})
        if any(n != new_size for n in dims.values()):
            sharded.append(attr)
    if not sharded:
        return
    layouts = dict(getattr(state, "zero1_layout", None) or {})
    missing = [a for a in sharded if str(a) not in layouts]
    if missing:
        raise RuntimeError(
            f"elastic: world resized {old_size}->{new_size} but tracked "
            f"state {missing} holds ZeRO-1 shards laid out for "
            f"{old_size} ranks and no bucket layout was attached to "
            f"reshard them — call hvd.elastic.note_zero1_layout(state, "
            f"attr, zero1_layout_from_params(...)) at setup "
            f"(docs/fault_tolerance.md 'Elastic resharding')."
        )
    from ..parallel import reshard as _reshard

    for attr in sharded:
        lay = layouts[str(attr)]
        if not hasattr(lay, "relayout"):
            lay = _reshard.Zero1Layout.from_dict(lay)
        if lay.n_shards != old_size:
            # The layout tracks the last reshard, not necessarily the
            # last generation — trust the state's actual leading dims.
            lay = lay.relayout(old_size)
        new_value, reports = _reshard.reshard_zero1_tree(
            getattr(state, attr), new_size, layouts={"": lay},
            trigger="resize",
        )
        setattr(state, attr, new_value)
        saved = getattr(state, "_saved", None)
        if isinstance(saved, dict) and attr in saved:
            saved[attr], _ = _reshard.reshard_zero1_tree(
                saved[attr], new_size, layouts={"": lay},
                trigger="resize",
            )
        layouts[str(attr)] = lay.relayout(new_size)
        for rep in reports:
            logger.info(
                "elastic: resharded %r zero1 state %d->%d shards for "
                "the new generation (%d bytes)", attr, rep["n_old"],
                rep["n_new"], rep["moved_bytes"],
            )
    state.zero1_layout = layouts
