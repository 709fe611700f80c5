"""The learner's dispatch protocol: one leased chunk, produced on the
worker fleet.

:class:`ChunkDispatcher` is what the experience transport's producer
(``trlx_tpu/exp/rollout.py``) calls for a chunk it holds a lease on.
It publishes the policy snapshot if due, waits for the fleet to be
ready against ``fleet.min_workers``, selects a worker, dispatches the
prompt batch with the replay snapshot, watches the worker's membership
heartbeats while it generates, re-dispatches on silence, gives up at
the dispatch deadline, and adopts the delivered payload with the
worker's post-production snapshot. The answer is "delivered" or
"produce here": a degraded fleet is invisible in the loss stream,
because the caller then produces the chunk in-process from the same
snapshot. The primitives (membership, broadcast, assignment and
delivery messages) are :class:`~trlx_tpu.fleet.coordinator.
FleetCoordinator`'s.

Everything the trainer owns arrives as an argument; nothing here
imports ``trlx_tpu.trainer``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

from trlx_tpu.fleet import serde
from trlx_tpu.fleet.coordinator import FleetCoordinator
from trlx_tpu.utils import logging
from trlx_tpu.utils.guardrails import FLEET_SIGNAL

logger = logging.get_logger(__name__)


@dataclass(kw_only=True)
class ChunkDispatcher:
    """Apart from ``coordinator``, the fields are the trainer's side."""

    coordinator: FleetCoordinator
    # the live policy parameters and their version
    params: Callable[[], Any]
    policy_version: Callable[[], int]
    # the producer-side replay state (RNG, reward moments), its restore
    snapshot: Callable[[], Dict[str, Any]]
    restore: Callable[[Dict[str, Any]], None]
    # the trainer's hang doctor and chaos handle, and the guardrail
    # monitor's ``trip(signal, detail)``
    watchdog: Any
    chaos: Any
    trip: Callable[[str, str], None]

    def stats_summary(self) -> Dict[str, Any]:
        return self.coordinator.stats_summary()

    def _post_publish(self, path: str) -> None:
        """Chaos seam for ``broadcast_corrupt``: fired once per landed
        weight-snapshot publish, AFTER the atomic rename — only the
        workers' manifest verification can catch the flipped bit."""
        if self.chaos is not None and self.chaos.consult("broadcast_corrupt"):
            self.chaos.corrupt_broadcast(path)

    def _degrade(self, why: str) -> bool:
        """Record a healthy->degraded transition and trip the ``fleet``
        guardrail signal (once per transition — a long outage must not
        spam the escalation ladder). Always returns False so callers
        can ``return self._degrade(...)`` out of the fleet path."""
        if self.coordinator.note_degraded(why):
            self.trip(
                FLEET_SIGNAL,
                f"rollout fleet degraded: {why} — falling back to "
                "in-process production (bit-equal to the fleet-less run)",
            )
        return False

    def _ready(self, iter_count: int) -> bool:
        """Evict silent workers, then gate on ``fleet.min_workers``.
        The FIRST production waits out ``fleet.startup_timeout_s`` for
        the fleet to register (workers launch in parallel with the
        learner's compile, so "not there yet" is the common case) — a
        fleet that never comes up degrades instead of wedging the run."""
        fleet, cfg = self.coordinator, self.coordinator.cfg
        deadline = (
            None if fleet._waited_startup
            else time.time() + cfg.startup_timeout_s
        )
        fleet._waited_startup = True
        while True:
            fleet.registry.evict_silent()
            if len(fleet.live_workers()) >= cfg.min_workers:
                return True
            if deadline is None or time.time() >= deadline:
                return False
            self.watchdog.beat("rollout", step=iter_count)
            time.sleep(cfg.poll_s)

    def produce(
        self, exp, lease, snap: Dict[str, Any], batch, iter_count: int,
        wait: Callable[[float], None],
    ) -> bool:
        """Produce the leased chunk on the worker fleet: publish the
        policy snapshot if due, dispatch the prompt batch + replay
        snapshot to a live worker, watch its membership heartbeats
        while it generates, and hand the delivered payload to the
        transport ``exp`` under the learner's own lease (``snap`` is
        the lease's replay snapshot, ``wait`` the transport's bounded-
        wait callback). A worker that goes silent mid-chunk is evicted
        and the chunk re-dispatched with the SAME snapshot
        (bit-identical regeneration). Returns False — after tripping
        the ``fleet`` signal once per transition — when the fleet is
        below ``min_workers`` (or a dispatch timed out); the caller
        then produces the chunk in-process from the same snapshot, so
        degradation is invisible in the loss stream."""
        fleet, cfg = self.coordinator, self.coordinator.cfg
        if self.chaos is not None and self.chaos.consult("hub_crash"):
            # chaos: the transport hub dies and is relaunched EMPTY
            # before this production — workers re-register on their
            # next beat, this chunk's dispatch gets a fresh attempt
            # number, and any in-flight delivery re-posts through the
            # dedup
            fleet.crash_hub()
        # publish before the readiness gate: workers that are still
        # attaching need the snapshot to produce anything at all. But a
        # DEGRADED fleet with no registered workers at all has no
        # consumers — skip the full-model snapshot (host copy + npz +
        # sha256 + fsync per policy version) until a registration
        # reappears, or a dead fleet taxes every remaining cycle
        if not fleet.degraded or fleet.registry.worker_records():
            fleet.ensure_published(
                self.policy_version(),
                lambda: serde.params_to_arrays(self.params()),
                post_publish=self._post_publish,
            )
        if not self._ready(iter_count):
            return self._degrade(
                f"{len(fleet.live_workers())} live workers < "
                f"fleet.min_workers={cfg.min_workers}"
            )
        fleet.note_recovered()
        chunk_id = lease.chunk_id

        def degrade_dispatched(why: str) -> bool:
            # abandon the outstanding dispatch: a later-rejoining
            # evicted worker must not burn a generation on a chunk the
            # learner is about to produce in-process, and its late
            # delivery must not linger to collide with a future
            # regeneration of the same id. The lease goes back to the
            # learner — IT is the producer from here on, and expiry
            # logs should say so
            fleet.clear_chunk(chunk_id)
            exp.reassign(lease, exp.owner)
            return self._degrade(why)
        # a previous incarnation/attempt may have left a delivery for
        # this seq (learner restart, staleness re-dispatch): the replay
        # contract makes a same-snapshot leftover bit-identical, but a
        # staleness regeneration must NOT consume the old samples —
        # clear and regenerate, which is correct for both
        fleet.clear_chunk(chunk_id)
        arrays, prompt_meta = serde.prompt_batch_to_arrays(batch)
        # the trainer's state == the replay snapshot at this point (a
        # re-dispatch restored it before calling here), so the wire
        # snapshot is exactly what an in-process production would
        # consume
        live = self.snapshot()
        wire_meta = {
            "iter_count": int(iter_count),
            "snapshot": serde.snapshot_to_wire(live),
            "prompt_metadata": prompt_meta,
        }
        tried: Tuple[str, ...] = ()
        valid_attempts = set()

        def assign(to: str) -> bool:
            # every dispatch of the chunk is a fresh attempt; False on a
            # transport outage
            attempt = fleet.next_attempt(chunk_id)
            valid_attempts.add(attempt)
            exp.reassign(lease, to)
            return fleet.dispatch(chunk_id, attempt, to, wire_meta, arrays)

        worker = fleet.select_worker()
        if worker is None:
            return self._degrade("no dispatchable worker")
        if not assign(worker):
            return degrade_dispatched(
                f"transport outage dispatching chunk {chunk_id}"
            )
        deadline = time.time() + cfg.dispatch_timeout_s
        # delivery is polled every tick, but the membership scan
        # (dir listing + one JSON parse per worker record) only needs
        # the TTL's resolution — on a shared/remote filesystem the
        # difference is thousands of metadata reads per chunk
        scan_every = max(cfg.worker_ttl_s / 4.0, cfg.poll_s)
        next_scan = 0.0
        while True:
            self.watchdog.beat("rollout", step=iter_count)
            exp.heartbeat(lease)
            msg = fleet.poll_delivery(chunk_id)
            if msg is not None:
                if int(msg[0].get("attempt", -1)) in valid_attempts:
                    break
                # a lingering worker's late delivery from an attempt
                # ABANDONED before this production (a staleness
                # regeneration reuses the chunk id with a NEW snapshot):
                # consuming it would replay the exact payload the gate
                # refused. Drop the payload only — the outstanding
                # assignment stays so the current worker isn't stranded
                fleet.clear_delivery(chunk_id)
                msg = None
            if time.time() >= next_scan:
                next_scan = time.time() + scan_every
                fleet.registry.evict_silent()
                lost = worker not in fleet.live_workers()
            else:
                lost = False
            if lost:
                # the producing worker died / partitioned / got
                # quarantined mid-chunk: re-dispatch elsewhere with the
                # same snapshot (regeneration is bit-identical, so the
                # consumed stream never sees the loss)
                tried = tried + (worker,)
                if len(fleet.live_workers()) < cfg.min_workers:
                    return degrade_dispatched(
                        f"worker {worker!r} lost mid-chunk {chunk_id} "
                        "and the live fleet fell below min_workers"
                    )
                worker = (
                    fleet.select_worker(exclude=tried)
                    or fleet.select_worker()  # all live ones tried: retry the set
                )
                if worker is None:
                    return degrade_dispatched(
                        f"no dispatchable worker for chunk {chunk_id}"
                    )
                if not assign(worker):
                    return degrade_dispatched(
                        f"transport outage re-dispatching chunk {chunk_id}"
                    )
                deadline = time.time() + cfg.dispatch_timeout_s
                continue
            if time.time() >= deadline:
                # alive-but-wedged worker: the membership TTL never
                # fires, so this bound is the backstop. Evict (flap-
                # tracked) and degrade; the in-process regeneration is
                # bit-identical via the replay snapshot.
                fleet.registry.evict(
                    worker,
                    f"dispatch timeout: chunk {chunk_id} undelivered "
                    f"after {cfg.dispatch_timeout_s:g}s",
                )
                return degrade_dispatched(
                    f"chunk {chunk_id} timed out on worker {worker!r}"
                )
            time.sleep(cfg.poll_s)
        meta_d, arrays_d = msg
        # a consumed delivery breaks the producing worker's eviction
        # streak — flap quarantine means consecutive evictions, not
        # cumulative-forever
        fleet.registry.note_healthy(str(meta_d.get("worker", "")))
        rollout_batch = serde.rollout_from_arrays(arrays_d)
        stats: Dict[str, Any] = dict(meta_d.get("stats") or {})
        rows_local = int(meta_d["rows_local"])
        version = int(meta_d["policy_version"])
        # adopt the worker's post-production snapshot: the learner's
        # RNG/moments chain continues exactly as if it had produced the
        # chunk in-process — that adoption is what keeps the fleet path
        # bit-equal to method.exp.enabled
        self.restore(
            serde.snapshot_from_wire(meta_d["post_snapshot"], live["rng"])
        )
        exp.heartbeat(lease)
        with self.watchdog.phase("exp_wait", step=iter_count):
            exp.deliver(
                lease, version, (rollout_batch, stats, rows_local),
                meta={"snapshot": snap}, wait=wait,
            )
        fleet.clear_chunk(chunk_id)
        return True

    def finish(self, iter_count: int, budget: int) -> None:
        """The learner's loop has ended: write the fleet's clean-finish
        flag ONLY when the step budget is actually done — a preemption
        / stall / crash exit leaves the workers alive for the
        relaunched learner's membership-epoch re-attach handshake."""
        if iter_count >= budget:
            self.coordinator.shutdown("train budget reached")
            logger.info(
                "fleet: clean finish — %s", self.coordinator.stats_summary()
            )
        else:
            logger.info(
                "fleet: learner exiting at step %d < %d with the fleet "
                "left ATTACHED (workers re-register on the relaunch's "
                "membership epoch)", iter_count, budget,
            )
