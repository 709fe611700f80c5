"""The rollout loop's side of the experience transport: the in-process
consumer and producer behind the leased queue.

:class:`LeasedChunks` is the chunk source an online trainer's
collection loop pulls from when ``method.exp.enabled``: it hands the
loop the next in-order, admitted chunk and produces here whatever has
not been delivered yet. It owns the lease protocol (replay snapshots
and their restore, heartbeats at milestones, the wait under the
``exp_wait`` phase, reclaim of an expired lease, redelivery of a
retained prefetch), the staleness verdicts (admit, clip, reject and
re-dispatch) and the ``exp/*`` stats. A rollout fleet
(``trlx_tpu/fleet/dispatch.py``) plugs in behind the producer.

Fault-free it is bit-equal to the direct loop: the same prompt pulls,
the same RNG splits per generate, the same score math (the trainer's
one chunk-producing function), consumed in the same order (the queue
is in-order by construction; ``tests/test_exp_queue.py``).

Everything the trainer owns arrives as an argument; nothing here
imports ``trlx_tpu.trainer``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

from trlx_tpu.exp import transport as exp_transport
from trlx_tpu.exp.leases import Lease
from trlx_tpu.exp.transport import ExperienceTransport
from trlx_tpu.utils.guardrails import STALENESS_SIGNAL

# what the collection loop pushes and counts:
# (rollout_batch, stats, rows_local)
Payload = Tuple[Any, Dict[str, Any], int]


@dataclass(kw_only=True)
class LeasedChunks:
    """Consumer and in-process producer of one trainer's transport.
    Apart from ``exp`` and ``fleet``, the fields are the trainer's side."""

    exp: ExperienceTransport
    # ``(iter_count, clock, batch=..., generation=..., after_generate=...)
    # -> (payload, version)``: one finished chunk from a prompt batch or
    # from a generation dispatched earlier
    produce: Callable[..., Tuple[Payload, int]]
    # pull the next prompt batch off the stream
    next_batch: Callable[[], Any]
    # the cycle's prefetched generation ``(batch, gen_out, dispatch wall,
    # version)`` or None; taking it clears it
    take_prefetch: Callable[[], Optional[Tuple]]
    # the producer-side replay state (RNG, reward moments), its restore
    snapshot: Callable[[], Dict[str, Any]]
    restore: Callable[[Dict[str, Any]], None]
    # the live policy version, for admission
    policy_version: Callable[[], int]
    # ``(rollout_batch, over_stale) -> rollout_batch`` for
    # ``staleness.mode: clip`` (the method's importance-weight
    # recompute, or unit weights)
    staleness_weights: Callable[[Any, bool], Any]
    # the trainer's hang doctor and chaos handle, and the guardrail
    # monitor's ``trip(signal, detail)``
    watchdog: Any
    chaos: Any
    trip: Callable[[str, str], None]
    # a ``fleet.dispatch.ChunkDispatcher`` or None
    fleet: Any = None
    # a staleness-rejected chunk's new lease, to produce next
    _redispatch: Optional[Lease] = field(default=None, init=False)
    # the chunk next_chunk() handed out last (committed() advances the
    # cursor past it once its payload reached the store)
    _admitted: Any = field(default=None, init=False)

    def _consult(self, site: str) -> bool:
        return self.chaos is not None and self.chaos.consult(site)

    def _wait(self, iter_count: int) -> Callable[[float], None]:
        """Bounded-wait callback for transport waits (back-pressure,
        lease expiry): beat the ``exp_wait`` watchdog phase and sleep
        one poll — a genuinely wedged queue then trips the watchdog
        deadline instead of hanging undiagnosed."""

        def wait(poll_s: float) -> None:
            self.watchdog.beat("exp_wait", step=iter_count)
            time.sleep(poll_s)

        return wait

    # -- consumer side ---------------------------------------------------

    def next_chunk(self, iter_count: int, clock) -> Payload:
        """The next in-order chunk the admission gate lets through,
        producing (or re-producing) here whatever is not delivered."""
        exp = self.exp
        while True:
            chunk = exp.poll()
            if chunk is None:
                lease = self._lease_to_produce(iter_count)
                if lease is not None:
                    self._produce_under(lease, iter_count, clock)
                continue
            payload = self._admit(chunk)
            if payload is not None:
                self._admitted = chunk
                return payload

    def _lease_to_produce(self, iter_count: int) -> Optional[Lease]:
        exp = self.exp
        lease, self._redispatch = self._redispatch, None
        if lease is not None:
            return lease
        gap = exp.queue.next_undelivered()
        if exp.leases.get((exp.queue.epoch, gap)) is not None:
            # the next in-order chunk is leased but not delivered: its
            # producer died (or is slow). Wait out the lease TTL under
            # the exp_wait phase, then reclaim + re-dispatch.
            wait = self._wait(iter_count)
            with self.watchdog.phase("exp_wait", step=iter_count):
                while True:
                    reclaimed = exp.reclaim_expired()
                    if reclaimed:
                        return reclaimed[0]
                    wait(exp.cfg.wait_poll_s)
        lease = exp.begin_chunk(snapshot=self.snapshot())
        if self._consult("worker_death_mid_lease"):
            # chaos: the producer dies right after taking the lease —
            # before any side effect. Heartbeats stop; the next pass
            # waits out the TTL above and re-dispatches the chunk.
            exp.producer_died(lease)
            return None
        return lease

    def _admit(self, chunk) -> Optional[Payload]:
        exp, scfg = self.exp, self.exp.cfg.staleness
        verdict, staleness = exp.admit(chunk, self.policy_version())
        if staleness > scfg.max_staleness:
            self.trip(
                STALENESS_SIGNAL,
                f"chunk {chunk.chunk_id} is {staleness} policy "
                f"versions stale (> max {scfg.max_staleness}; "
                f"verdict: {verdict}) — the rollout producers are "
                "falling behind the learner",
            )
        if verdict == exp_transport.REJECT:
            # over-stale: drop the delivery and regenerate the chunk's
            # prompts with the current policy (the replay snapshot
            # keeps the regeneration deterministic). A chunk born from
            # the cycle prefetch retains its old samples in
            # snap["gen"] for lost-delivery replay — but a staleness
            # reject must NOT redeliver those verbatim (same samples,
            # same version -> an infinite reject/redeliver loop):
            # strip the retained generation, keep its prompt batch, so
            # the produce path re-samples with the live policy and
            # stamps the live version
            snap = chunk.meta.get("snapshot")
            if snap is not None and snap.get("gen") is not None:
                snap["batch"] = snap["gen"][0]
                snap["gen"] = None
            self._redispatch = exp.redispatch_rejected(chunk)
            return None
        rollout_batch, stats, rows_local = chunk.payload
        if verdict == exp_transport.ADMIT_CLIP:
            rollout_batch = self.staleness_weights(rollout_batch, True)
            stats["exp/staleness_clipped"] = 1.0
        elif scfg.mode == "clip":
            # uniform store pytree structure: every batch of a
            # clip-mode run carries weights (fresh chunks at 1)
            rollout_batch = self.staleness_weights(rollout_batch, False)
        stats["exp/staleness"] = float(staleness)
        return rollout_batch, stats, rows_local

    def committed(self) -> None:
        """The chunk ``next_chunk`` returned reached the store."""
        self.exp.committed(self._admitted)

    def abandon(self) -> None:
        """The cycle was abandoned (pre-emption): in-flight chunks and
        leases never train. Void them, so that the resumed run's
        replayed prompts produce fresh chunks under a new epoch."""
        self._redispatch = None
        self.exp.abort_epoch()

    def cycle_stats(self) -> Dict[str, float]:
        """The transport's health ledger (and the fleet's: dispatches,
        evictions, quarantines, degradations), which rides the same
        deferred stage as the rollout stats (host ints: free)."""
        out = {
            f"exp/{k}": float(v)
            for k, v in self.exp.stats_summary().items()
            if isinstance(v, (int, float))
        }
        if self.fleet is not None:
            out.update({
                f"fleet/{k}": float(v)
                for k, v in self.fleet.stats_summary().items()
                if isinstance(v, (int, float))
            })
        return out

    # -- producer side ---------------------------------------------------

    def _produce_under(self, lease: Lease, iter_count: int, clock) -> None:
        """Produce one chunk under ``lease`` and deliver it: pull the
        prompt chunk (or consume the cycle's overlap prefetch), sample,
        score+assemble, then offer to the queue with the lease's
        heartbeats at each milestone. Re-dispatched leases (attempt > 1
        or a staleness re-dispatch) restore the replay snapshot first,
        so the regenerated chunk is bit-identical to the lost one."""
        exp = self.exp
        snap = lease.meta if lease.meta is not None else {}
        lease.meta = snap
        if snap.get("rng") is not None:
            # no-op on a fresh attempt (the snapshot IS the live state);
            # on a re-dispatch it rewinds the producer-side effects so
            # the replay is bit-identical
            self.restore(snap)
        wait = self._wait(iter_count)
        batch = None
        # replaying a chunk originally produced from the cycle prefetch:
        # the generation (old params, old key) cannot be re-run —
        # redeliver the retained samples wholesale
        generation = snap.get("gen")
        if generation is None:
            generation = snap["gen"] = self.take_prefetch()
        if generation is None:
            batch = snap.get("batch")
            if batch is None:
                batch = snap["batch"] = self.next_batch()
            if self.fleet is not None and self.fleet.produce(
                exp, lease, snap, batch, iter_count, wait
            ):
                # produced + delivered by a fleet worker (the learner
                # adopted its post-production snapshot); the consumer
                # takes it from here
                return
        exp.heartbeat(lease)
        payload, version = self.produce(
            iter_count, clock, batch=batch, generation=generation,
            after_generate=lambda: exp.heartbeat(lease),
        )
        exp.heartbeat(lease)
        if self._consult("stale_flood"):
            # chaos: the chunk's staleness metadata is corrupted — its
            # recorded generation version lands far behind the live
            # policy, so the admission gate must reject (or clip) it
            version = version - (exp.cfg.staleness.max_staleness + 10)
        if self._consult("queue_wedge"):
            # chaos: the learner stops draining — the next offers see a
            # full queue and the bounded back-pressure wait must ride
            # it out under exp_wait heartbeats
            exp.wedge()
        with self.watchdog.phase("exp_wait", step=iter_count):
            exp.deliver(
                lease, version, payload, meta={"snapshot": snap}, wait=wait
            )
            if self._consult("duplicate_delivery"):
                # chaos: the producer's retry races its own success —
                # the same finished chunk is delivered twice; consumer
                # dedup must drop the redelivery
                exp.deliver(
                    lease, version, payload, meta={"snapshot": snap},
                    wait=wait,
                )
