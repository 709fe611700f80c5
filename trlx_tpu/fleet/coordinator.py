"""Learner-side fleet coordinator: dispatch, collect, degrade.

Sits between the experience transport's producer and the
cross-process worker fleet (the protocol that drives these primitives
for one chunk is ``fleet/dispatch.py``). The learner keeps owning the
transport lease for every chunk; the coordinator turns "produce this chunk" into
a dispatch message a registered worker executes, watches the worker's
membership heartbeats while it runs, and hands the delivered payload
back. A silent worker is evicted (flap-tracked, quarantined past
``fleet.flap_limit``) and the chunk re-dispatched with the SAME replay
snapshot — regeneration is bit-identical, so a worker death is
invisible in the consumed stream. When the live fleet falls below
``fleet.min_workers`` the coordinator reports DEGRADED and the trainer
falls back to in-process production (the ``fleet`` guardrail signal
trips once per transition).

Chunk messaging rides the pluggable transport (``exp/net.py``). On the
default shared-fs backend the layout under the fleet dir is the
original atomic-rename protocol, byte for byte::

    dispatch/e{epoch}_s{seq}_a{attempt}/   assignment for one worker
    chunks/e{epoch}_s{seq}/                the delivered chunk payload

On the tcp backend the same (topic, name) messages live in a
:class:`trlx_tpu.exp.net.TcpHub`, and the CONTROL PLANE — membership
records, the shutdown flag, the chunked weight broadcast — rides the
very same transport, so workers need no shared filesystem at all.

Delivery is naturally deduplicating: the chunk dir name carries no
attempt, so whichever attempt's rename lands first wins and the other
drops itself (both are bit-identical by the replay contract anyway).

Transport failures DEGRADE instead of crash: ``dispatch`` reports
False, polls read as not-yet-delivered, and the trainer's existing
below-min-workers ladder takes over (in-process fallback is
bit-identical by the replay contract). A learner-side chaos
``hub_crash`` relaunches the hub empty via :meth:`FleetCoordinator.
crash_hub` — recovery is re-registration (worker beats), fresh
dispatch attempts, and the put dedup for re-posted in-flight traffic.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from trlx_tpu.fleet.broadcast import BROADCAST_TOPIC, make_broadcast
from trlx_tpu.fleet.config import FleetConfig
from trlx_tpu.fleet.membership import MEMBERSHIP_RECORD, WorkerRegistry
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

DISPATCH_DIR = "dispatch"
CHUNKS_DIR = "chunks"
BROADCAST_DIR = BROADCAST_TOPIC


def chunk_name(chunk_id: Tuple[int, int]) -> str:
    return f"e{int(chunk_id[0])}_s{int(chunk_id[1])}"


class FleetCoordinator:
    def __init__(
        self,
        cfg: FleetConfig,
        root: str,
        owner: str = "learner",
        clock: Callable[[], float] = time.time,
        transport=None,
    ):
        from trlx_tpu.exp.net import (
            SharedFSTransport,
            base_transport,
            make_server_transport,
        )

        self.cfg = cfg
        self.root = root
        self._clock = clock
        # everything — chunk dispatch/delivery, membership records,
        # weight broadcast — rides the pluggable transport; the default
        # shared-fs backend reproduces the pre-interface layout byte
        # for byte. On the tcp backend the LEARNER hosts the hub
        # (workers connect with the same spec's host/port) unless
        # ``host_hub: false`` points at an external supervised hub.
        self.hub = None
        if transport is not None:
            self.transport = transport
            self.transport_spec = None  # caller-supplied: unknown wire
        else:
            self.hub, self.transport, self.transport_spec = (
                make_server_transport(cfg.transport, root)
            )
        shared_fs = isinstance(
            base_transport(self.transport), SharedFSTransport
        )
        if shared_fs:
            # golden layout only: a tcp-only learner must leave no
            # fleet directories behind (proof the workers never need
            # a shared path)
            os.makedirs(os.path.join(root, DISPATCH_DIR), exist_ok=True)
            os.makedirs(os.path.join(root, CHUNKS_DIR), exist_ok=True)
        self.registry = WorkerRegistry(
            root if shared_fs else self.transport,
            worker_ttl_s=cfg.worker_ttl_s,
            flap_limit=cfg.flap_limit,
            flap_backoff_s=cfg.flap_backoff_s,
            clock=clock,
        )
        self.broadcast = make_broadcast(
            self.transport, keep=cfg.broadcast_keep
        )
        # the attach handshake: bump the membership epoch so surviving
        # workers from a previous learner incarnation re-register
        self.membership_epoch = self.registry.open_epoch(owner)
        self.degraded = False
        self._waited_startup = False
        self._published_version: Optional[int] = None
        self._rr = 0  # round-robin cursor over the live set
        # per-chunk dispatch-attempt counter: every dispatch (first try,
        # eviction re-dispatch, staleness regeneration) gets a fresh
        # attempt number, so assignment dirs never collide and "highest
        # attempt wins" stays well-defined on the worker side
        self._attempts: Dict[str, int] = {}
        self.stats: Dict[str, int] = {
            "dispatched": 0,
            "delivered": 0,
            "redispatches": 0,
            "degradations": 0,
            "recoveries": 0,
            "hub_restarts": 0,
            "transport_errors": 0,
        }

    # -- weight broadcast -------------------------------------------------

    def ensure_published(
        self,
        version: int,
        arrays_fn: Callable[[], Dict[str, np.ndarray]],
        post_publish: Optional[Callable[[str], None]] = None,
    ) -> None:
        """Publish the policy snapshot for ``version`` if due
        (``fleet.broadcast_every`` versions since the last publish).
        ``post_publish(path)`` is the chaos seam (``broadcast_corrupt``
        bit-flips the landed snapshot). A transport outage mid-publish
        leaves the cursor UNMOVED so the next call republishes; workers
        keep their held version through the gap (staleness-gated)."""
        if self._published_version is not None and (
            version - self._published_version < self.cfg.broadcast_every
        ):
            return
        try:
            path = self.broadcast.publish(version, arrays_fn())
        except (OSError, ConnectionError) as e:
            self.stats["transport_errors"] += 1
            logger.error(
                "fleet: broadcast publish of version %d failed (%s); "
                "will retry next cycle", version, e,
            )
            return
        self._published_version = version
        if post_publish is not None:
            post_publish(path)

    def reset_published(self) -> None:
        """Forget the publish cursor. An in-process restore (guardrail
        rollback, explicit load) can move the policy version BACKWARDS;
        a cursor left ahead of it would make ensure_published skip
        forever and workers would keep generating with the discarded
        weights — admitted as non-stale, since their version reads as
        newer. The next ensure_published republishes unconditionally
        (publish() replaces a leftover same-version tree wholesale:
        the restored params ARE that version)."""
        self._published_version = None

    @property
    def broadcast_version(self) -> Optional[int]:
        return self._published_version

    # -- membership-facing helpers ---------------------------------------

    def live_workers(self) -> List[str]:
        return self.registry.live_workers()

    def select_worker(self, exclude: Tuple[str, ...] = ()) -> Optional[str]:
        """Round-robin over the live, non-excluded set (excluded = the
        worker(s) already tried for this chunk)."""
        live = [w for w in self.live_workers() if w not in exclude]
        if not live:
            return None
        self._rr += 1
        return live[self._rr % len(live)]

    def note_degraded(self, detail: str) -> bool:
        """Record a healthy->degraded transition. Returns True exactly
        once per transition (the caller trips the ``fleet`` guardrail
        signal on True, so a long outage is one trip, not thousands)."""
        if self.degraded:
            return False
        self.degraded = True
        self.stats["degradations"] += 1
        logger.error("fleet DEGRADED: %s — falling back to in-process "
                     "rollout production", detail)
        return True

    def note_recovered(self) -> None:
        if self.degraded:
            self.degraded = False
            self.stats["recoveries"] += 1
            logger.warning(
                "fleet recovered: %d live workers — resuming fleet "
                "production", len(self.live_workers()),
            )

    # -- chunk dispatch / delivery ---------------------------------------

    def next_attempt(self, chunk_id: Tuple[int, int]) -> int:
        name = chunk_name(chunk_id)
        self._attempts[name] = self._attempts.get(name, 0) + 1
        return self._attempts[name]

    def dispatch(
        self,
        chunk_id: Tuple[int, int],
        attempt: int,
        worker: str,
        meta: Dict[str, Any],
        arrays: Dict[str, np.ndarray],
    ) -> bool:
        """Post the assignment. False on a transport outage — the
        caller treats it like an empty live set (degrade to in-process
        production, bit-identical by the replay contract) and the
        attempt number is simply never answered."""
        name = f"{chunk_name(chunk_id)}_a{int(attempt)}"
        try:
            self.transport.put(
                DISPATCH_DIR, name,
                {**meta, "worker": worker, "attempt": int(attempt),
                 "chunk_id": list(chunk_id)},
                arrays,
                meta_name="assignment.json",
            )
        except (OSError, ConnectionError) as e:
            self.stats["transport_errors"] += 1
            logger.error(
                "fleet: dispatch of chunk %s attempt %d failed (%s)",
                chunk_id, attempt, e,
            )
            return False
        self.stats["dispatched"] += 1
        if attempt > 1:
            self.stats["redispatches"] += 1
        logger.info(
            "fleet: dispatched chunk %s attempt %d to worker %r",
            chunk_id, attempt, worker,
        )
        return True

    def poll_delivery(
        self, chunk_id: Tuple[int, int]
    ) -> Optional[Tuple[Dict[str, Any], Dict[str, np.ndarray]]]:
        try:
            msg = self.transport.get(
                CHUNKS_DIR, chunk_name(chunk_id), meta_name="chunk.json"
            )
        except (OSError, ConnectionError):
            # mid-outage reads as not-yet-delivered; the poll loop's
            # eviction scan / dispatch timeout owns escalation
            self.stats["transport_errors"] += 1
            return None
        if msg is not None:
            self.stats["delivered"] += 1
        return msg

    def clear_delivery(self, chunk_id: Tuple[int, int]) -> None:
        """Drop ONLY the delivered payload (a lingering worker's late
        delivery from an abandoned attempt) — the outstanding dispatch
        assignment stays, so the currently-assigned worker is not
        stranded."""
        try:
            self.transport.delete(CHUNKS_DIR, chunk_name(chunk_id))
        except (OSError, ConnectionError):
            self.stats["transport_errors"] += 1

    def clear_chunk(self, chunk_id: Tuple[int, int]) -> None:
        """Drop a consumed chunk's delivery + dispatch messages (the
        transport queue owns the payload now; leftovers would only
        confuse a postmortem — and on a volatile hub a restart clears
        them anyway, so failure here is ignorable)."""
        name = chunk_name(chunk_id)
        try:
            self.transport.delete(CHUNKS_DIR, name)
            self.transport.delete_prefix(DISPATCH_DIR, f"{name}_a")
        except (OSError, ConnectionError):
            self.stats["transport_errors"] += 1

    # -- hub lifecycle (chaos + recovery) --------------------------------

    def crash_hub(self) -> bool:
        """Chaos ``hub_crash`` body: crash-and-relaunch the learner-
        hosted hub with ALL volatile state lost — the worst observable
        outcome of a supervised hub restart. No-op (False) when the
        fleet isn't hosting one (shared-fs, or external host_hub=false
        hub whose lifecycle the supervisor owns)."""
        if self.hub is None:
            return False
        self.hub.restart()
        self.stats["hub_restarts"] += 1
        # volatile records are gone: re-stamp the attach epoch so
        # workers' next membership poll sees the SAME epoch (no forced
        # re-register storm) and the clean-finish semantics survive
        try:
            self.registry.control.put_record(
                "", MEMBERSHIP_RECORD,
                {"epoch": self.membership_epoch, "learner": "learner",
                 "stamped_at": self._clock()},
            )
        except (OSError, ConnectionError):
            self.stats["transport_errors"] += 1
        return True

    # -- persistence / teardown ------------------------------------------

    def state(self) -> Dict[str, Any]:
        """What the checkpoint persists (state.json ``fleet`` section):
        the membership epoch a resumed learner must bump past, the
        last broadcast version (verify_ckpt.py's torn-commit check
        compares it against the exp cursor's policy version) and the
        publish cadence that bounds their legal gap."""
        return {
            "membership_epoch": int(self.membership_epoch),
            "broadcast_version": (
                -1 if self._published_version is None
                else int(self._published_version)
            ),
            "broadcast_every": int(self.cfg.broadcast_every),
        }

    def shutdown(
        self, reason: str = "clean finish",
        grace_s: Optional[float] = None,
    ) -> None:
        """Write the clean-finish flag, then tear down. When this
        learner hosts the hub the flag lives in HUB memory — closing
        immediately would take it away before workers poll it — so we
        wait (bounded by ``grace_s``, default ``2 * worker_ttl_s``)
        until every current-epoch worker's heartbeat goes silent,
        i.e. every worker has seen the flag and exited its beat
        loop."""
        self.registry.shutdown(reason)
        if self.hub is None:
            return
        grace = (
            float(grace_s) if grace_s is not None
            else max(2.0 * self.cfg.worker_ttl_s, 1.0)
        )
        beat_gap = 3.0 * max(
            min(self.cfg.worker_ttl_s / 4.0, 1.0), 0.02
        )
        deadline = time.time() + grace
        while time.time() < deadline:
            recs = self.registry.worker_records()
            now = time.time()  # wall clock — matches worker beats
            if all(
                now - rec.get("last_beat", 0.0) > beat_gap
                for rec in recs.values()
                if rec.get("epoch") == self.membership_epoch
            ):
                break
            time.sleep(max(self.cfg.poll_s, 0.02))
        self.hub.close()

    def stats_summary(self) -> Dict[str, Any]:
        return {
            **self.stats,
            **{f"membership_{k}": v for k, v in self.registry.stats.items()},
            **{f"broadcast_{k}": v for k, v in self.broadcast.stats.items()},
            "live_workers": len(self.live_workers()),
            "membership_epoch": self.membership_epoch,
            "degraded": int(self.degraded),
        }
