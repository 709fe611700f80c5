"""Experience transport orchestrator: leases + queue + admission gate.

The object the rollout loop's chunk source drives (``exp/rollout.py``
``LeasedChunks`` is the in-process producer/consumer pair; the rollout
fleet plugs in behind it). One instance owns the delivery state machine:

  producer side   :meth:`begin_chunk` (lease + replay snapshot) ->
                  produce -> :meth:`heartbeat` at milestones ->
                  :meth:`deliver` (bounded back-pressure wait, lease
                  release). A producer that dies mid-lease simply stops
                  heartbeating; :meth:`reclaim_expired` hands the chunk
                  to a live producer with the replay snapshot intact.
  consumer side   :meth:`poll` (in-order, deduped) -> :meth:`admit`
                  (staleness gate: version-at-generation vs
                  version-at-consumption) -> push to the store ->
                  :meth:`committed` (cursor advance — the position the
                  checkpoint persists).

The bounded waits (back-pressure, lease expiry) take a ``wait``
callable so the trainer can thread watchdog heartbeats through them —
a queue wedge then shows up as the ``exp_wait`` phase going silent,
never as an undiagnosable hang.

This class is in-process delivery STATE (ordering, dedup, staleness,
cursors); the bytes that cross a process/machine boundary ride the
pluggable topic transport in :mod:`trlx_tpu.exp.net` (shared-fs or
tcp) — the fleet's chunk messaging and the serving tier's
request/response traffic both use it.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from trlx_tpu.exp.leases import Lease, LeaseTable
from trlx_tpu.exp.queue import (
    OFFER_ACCEPTED,
    OFFER_DUPLICATE,
    OFFER_FULL,
    OFFER_STALE_EPOCH,
    ExpConfig,
    ExperienceChunk,
    ExperienceQueue,
)
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

ADMIT = "admit"
ADMIT_CLIP = "clip"
REJECT = "reject"


class ExperienceTransport:
    """Lease-based at-least-once production feeding an ordered,
    deduplicating queue, with a staleness admission gate in front of
    the consumer."""

    def __init__(
        self,
        cfg: ExpConfig,
        owner: str = "producer-0",
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ):
        self.cfg = cfg
        self.owner = owner
        self._clock = clock
        self._sleep = sleep
        self.queue = ExperienceQueue(cfg.max_depth)
        self.leases = LeaseTable(cfg.lease_ttl_s, clock=clock)
        # highest seq ever leased in the current epoch: production
        # allocates the next one (re-dispatch reclaims, never re-leases)
        self._produced_seq = 0
        # chaos queue_wedge: the next N offers report full regardless of
        # real depth (a consumer that stopped draining, from the
        # producer's point of view)
        self._wedged_offers = 0
        self.stats: Dict[str, int] = {
            "backpressure_waits": 0,
            "staleness_rejects": 0,
            "staleness_clips": 0,
            "redispatches": 0,
        }

    # -- producer side ---------------------------------------------------

    def begin_chunk(self, snapshot: Optional[Dict[str, Any]] = None) -> Lease:
        """Lease the next chunk seq for production. ``snapshot`` is the
        replay state a re-dispatch restores (RNG / running-moment
        snapshot + the stream position) — it stays on the lease, so a
        producer death loses nothing but the wasted work."""
        self._produced_seq += 1
        return self.leases.acquire(
            (self.queue.epoch, self._produced_seq), self.owner,
            meta=snapshot,
        )

    def heartbeat(self, lease: Lease) -> None:
        self.leases.heartbeat(lease.chunk_id)

    def reassign(self, lease: Lease, producer: str) -> None:
        """Relabel WHO is generating the leased chunk (the rollout
        fleet: the learner keeps holding the lease on the worker's
        behalf, but expiry logs and postmortems should name the worker
        actually producing, not the learner process)."""
        lease.owner = producer
        self.stats["reassignments"] = self.stats.get("reassignments", 0) + 1

    def producer_died(self, lease: Lease) -> None:
        """The producer holding ``lease`` died mid-chunk (chaos
        ``worker_death_mid_lease``): its heartbeats stop; the lease
        expires on TTL and :meth:`reclaim_expired` re-dispatches."""
        self.leases.mark_dead(lease.chunk_id)
        logger.warning(
            "exp transport: producer %r died holding the lease on chunk "
            "%s — the lease will expire in <= %.3gs and the chunk will "
            "be re-dispatched", lease.owner, lease.chunk_id,
            self.cfg.lease_ttl_s,
        )

    def wedge(self, offers: int = 2) -> None:
        """Chaos ``queue_wedge`` body: make the next ``offers``
        deliveries see a full queue, exercising the back-pressure wait
        path (bounded, watchdog-beating) without a second thread."""
        self._wedged_offers += int(offers)

    def deliver(
        self,
        lease: Lease,
        policy_version: int,
        payload: Any,
        meta: Optional[Dict[str, Any]] = None,
        wait: Optional[Callable[[float], None]] = None,
    ) -> str:
        """Offer the finished chunk, waiting out back-pressure (bounded
        by ``offer_timeout_s``; each poll calls ``wait(poll_s)`` so the
        caller can beat its watchdog phase), then release the lease.
        Returns the final offer status (``accepted`` or ``duplicate`` —
        dedup means a redelivery is SUCCESS from the producer's view)."""
        chunk = ExperienceChunk(
            chunk_id=lease.chunk_id, policy_version=int(policy_version),
            payload=payload, meta=dict(meta or {}),
        )
        deadline = (
            self._clock() + self.cfg.offer_timeout_s
            if self.cfg.offer_timeout_s > 0 else None
        )
        while True:
            if self._wedged_offers > 0:
                self._wedged_offers -= 1
                status = OFFER_FULL
            else:
                status = self.queue.offer(chunk)
            if status != OFFER_FULL:
                break
            self.stats["backpressure_waits"] += 1
            if deadline is not None and self._clock() >= deadline:
                raise RuntimeError(
                    f"exp transport: back-pressure wait on chunk "
                    f"{chunk.chunk_id} exceeded offer_timeout_s="
                    f"{self.cfg.offer_timeout_s} (queue depth "
                    f"{self.queue.depth}/{self.queue.max_depth} — the "
                    "learner stopped draining)"
                )
            (wait or self._sleep)(self.cfg.wait_poll_s)
        self.leases.release(lease.chunk_id)
        return status

    # -- consumer side ---------------------------------------------------

    def poll(self) -> Optional[ExperienceChunk]:
        """The next in-order chunk, or None (not delivered yet)."""
        return self.queue.poll()

    def reclaim_expired(self) -> List[Lease]:
        """Reclaim every expired lease for re-dispatch (fresh clock,
        attempt+1, replay snapshot intact). The caller regenerates each
        returned lease's chunk."""
        out = []
        for lease in self.leases.expired():
            out.append(self.leases.reclaim(lease.chunk_id, self.owner))
            self.stats["redispatches"] += 1
        return out

    def admit(
        self, chunk: ExperienceChunk, current_version: int
    ) -> Tuple[str, int]:
        """Staleness admission gate. Returns ``(verdict, staleness)``:

        - ``admit``  — within ``max_staleness`` (the overlap_rollouts
          prefetch is 1 by construction); train on it as-is.
        - ``clip``   — over-stale but ``mode: clip``: train with
          IMPACT-style clipped importance weights (the trainer threads
          the per-token correction into the surrogate).
        - ``reject`` — over-stale, ``mode: reject``: the chunk is
          dropped from the buffer (cursor unmoved) and must be
          re-dispatched/regenerated with the current policy.
        """
        staleness = int(current_version) - int(chunk.policy_version)
        scfg = self.cfg.staleness
        if staleness <= scfg.max_staleness:
            return ADMIT, staleness
        if scfg.mode == "clip":
            self.stats["staleness_clips"] += 1
            return ADMIT_CLIP, staleness
        self.stats["staleness_rejects"] += 1
        self.queue.discard(chunk)
        return REJECT, staleness

    def committed(self, chunk: ExperienceChunk) -> None:
        """The chunk's payload reached the store: advance the consumer
        cursor (the position the checkpoint persists)."""
        self.queue.commit(chunk)

    def redispatch_rejected(self, chunk: ExperienceChunk) -> Lease:
        """Re-lease a staleness-rejected chunk's seq for regeneration
        (the original lease was released at delivery). The replay
        snapshot comes from the chunk's meta, so the regeneration is
        deterministic."""
        self.stats["redispatches"] += 1
        return self.leases.acquire(
            chunk.chunk_id, self.owner,
            meta=chunk.meta.get("snapshot"),
        )

    # -- epoch + persistence ---------------------------------------------

    def abort_epoch(self) -> int:
        """Guardrail requeue / rollback rebuilt the data stream: void
        every in-flight chunk and lease; seqs restart under the new
        epoch (replayed prompts produce fresh chunks)."""
        self.leases.drop_all()
        self._produced_seq = 0
        return self.queue.advance_epoch()

    def state_dict(self) -> Dict[str, Any]:
        """What the checkpoint persists (inside the atomic state.json
        commit): the committed consumer cursor and its epoch. Produced-
        but-unconsumed chunks deliberately do NOT persist — the prompt
        stream regenerates them on resume, which is what makes the
        cursor alone a complete recovery point."""
        return {
            "epoch": int(self.queue.epoch),
            "cursor": int(self.queue.cursor),
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.queue.load_cursor(
            state.get("epoch", 0), state.get("cursor", 0)
        )
        self._produced_seq = self.queue.cursor

    def stats_summary(self) -> Dict[str, Any]:
        return {
            **{f"queue_{k}": v for k, v in self.queue.stats.items()},
            **{f"lease_{k}": v for k, v in self.leases.stats.items()},
            **self.stats,
            "depth": self.queue.depth,
            "cursor": self.queue.cursor,
            "epoch": self.queue.epoch,
        }
