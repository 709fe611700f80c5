"""The rollout worker: a cross-process producer behind the transport.

A worker is a full (but learner-less) PPO trainer: same config, same
jitted sampler and score path, driven by dispatch messages instead of
a training loop. Per assignment it restores the replay snapshot the
learner attached (RNG + reward running-moments + ref stats), refreshes
its policy weights from the versioned broadcast, generates and scores
the chunk through the SAME ``_produce_chunk`` the learner uses,
and delivers the payload plus its post-production snapshot — which the
learner adopts, so the learner's RNG/moments chain is bit-identical to
having produced the chunk in-process.

Liveness: a daemon thread rewrites the membership record every
fraction of ``fleet.worker_ttl_s`` — process death (or a chaos
partition, which pauses the thread) silences it and the learner
evicts + re-dispatches. A wedged-but-alive worker is the learner's
``fleet.dispatch_timeout_s`` backstop's job.

Entry point::

    from trlx_tpu.fleet.worker import run_worker
    run_worker(config=my_trl_config, reward_fn=my_reward_fn)

``config`` must equal the learner's (model/tokenizer/seed/method) —
the worker rebuilds the frozen reference from it, and a drifted config
shows up as a broadcast param-leaf mismatch, not silent divergence.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

from trlx_tpu.fleet import serde
from trlx_tpu.fleet.broadcast import BroadcastCorrupt, make_broadcast
from trlx_tpu.fleet.config import FleetConfig
from trlx_tpu.fleet.coordinator import CHUNKS_DIR, DISPATCH_DIR
from trlx_tpu.fleet.membership import (
    read_membership,
    shutdown_requested,
    write_worker_record,
)
from trlx_tpu.utils import logging
from trlx_tpu.utils.resilient import retry_call

logger = logging.get_logger(__name__)


class FleetWorker:
    def __init__(
        self,
        trainer,
        root: str,
        cfg: FleetConfig,
        worker_id: Optional[str] = None,
        max_chunks: Optional[int] = None,
        transport=None,
    ):
        from trlx_tpu.exp.net import FaultyTransport, make_transport

        self.trainer = trainer
        self.root = root
        self.cfg = cfg
        # ALL cross-process traffic — chunk assignment/delivery AND the
        # control plane (membership records, shutdown flag, weight
        # broadcast) — rides one transport (exp/net.py): must be the
        # SAME backend the learner's coordinator built
        self.transport = transport or make_transport(cfg.transport, root)
        if trainer.chaos is not None and not isinstance(
            self.transport, FaultyTransport
        ):
            # an armed chaos monkey drives this worker's LINK through
            # the net_drop / net_partition sites (the per-link fault
            # injector wraps every transport op this worker makes)
            self.transport = FaultyTransport(
                self.transport, chaos=trainer.chaos
            )
        self.worker_id = worker_id or f"worker-{os.getpid()}"
        self.max_chunks = max_chunks
        self.broadcast = make_broadcast(
            self.transport, keep=cfg.broadcast_keep, chaos=trainer.chaos
        )
        self._held_version: Optional[int] = None
        self._epoch: Optional[int] = None
        self._joined_at: Optional[float] = None
        self._produced = 0
        # ASSIGNMENT entries (chunk + attempt) this process already
        # produced — keyed per attempt, not per chunk, so a staleness
        # regeneration re-dispatched to this same worker is picked up
        # instead of mistaken for the delivered original
        self._done: set = set()
        # liveness beats ride a daemon thread so a long compile inside
        # the first generate cannot read as death; a chaos partition
        # pauses it (beats stop = what the learner can observe)
        self._beat_stop = threading.Event()
        self._beat_pause = threading.Event()

    # -- liveness ---------------------------------------------------------

    def _beat_once(self) -> None:
        if self._epoch is None or self._beat_pause.is_set():
            return
        write_worker_record(
            self.transport, self.worker_id, self._epoch,
            self._held_version, joined_at=self._joined_at,
        )

    def _beat_loop(self) -> None:
        interval = max(min(self.cfg.worker_ttl_s / 4.0, 1.0), 0.02)
        while not self._beat_stop.is_set():
            try:
                self._beat_once()
            except (OSError, ConnectionError):
                # transient shared-fs hiccup / tcp drop / hub restart:
                # the next beat retries — and doubles as the
                # RE-REGISTRATION that recovers from a hub losing its
                # volatile records
                pass
            self._beat_stop.wait(interval)

    # -- membership -------------------------------------------------------

    def _sync_membership(self) -> bool:
        """Poll the membership record; on an epoch bump, re-register
        under the new epoch (the learner-restart handshake). Returns
        False until a learner has attached at all (an unreachable
        control plane reads the same: keep polling)."""
        m = read_membership(self.transport)
        if m is None:
            return False
        epoch = int(m.get("epoch", 0))
        if epoch != self._epoch:
            self._epoch = epoch
            self._joined_at = time.time()
            self._beat_once()  # register immediately, not next tick
            logger.info(
                "fleet worker %r: registered under membership epoch %d",
                self.worker_id, epoch,
            )
        return True

    # -- weights ----------------------------------------------------------

    def _refresh_weights(self) -> None:
        """Adopt the CURRENT broadcast snapshot if it moved, with
        retry/backoff; a snapshot that stays corrupt/torn after the
        retries is SKIPPED and the previous version kept — the chunks
        then carry the older policy version and flow through the
        ``exp.staleness`` gate (off-policy correction, never wrong
        weights)."""
        current = self.broadcast.current_version()
        if current is None or current == self._held_version:
            return
        try:
            version, arrays = retry_call(
                self.broadcast.fetch, retries=2,
                base_delay=self.cfg.poll_s, max_delay=1.0,
                description="broadcast fetch",
            )
        except (BroadcastCorrupt, OSError, ValueError) as e:
            logger.error(
                "fleet worker %r: broadcast refresh failed (%s) — "
                "keeping policy version %s", self.worker_id, e,
                self._held_version,
            )
            return
        t = self.trainer
        t.params = serde.load_params_like(t.params, arrays)
        t._policy_version = version
        self._held_version = version
        logger.info(
            "fleet worker %r: refreshed weights to policy version %d",
            self.worker_id, version,
        )

    # -- assignments ------------------------------------------------------

    def _scan_assignments(self) -> List[str]:
        try:
            entries = self.transport.list(DISPATCH_DIR)
        except (OSError, ConnectionError):
            return []
        out = []
        for entry in entries:
            if "_a" not in entry:
                continue
            chunk = entry.rsplit("_a", 1)[0]
            if entry in self._done or self._delivered(chunk):
                continue
            out.append(entry)
        return out

    def _delivered(self, chunk: str) -> bool:
        try:
            return (
                self.transport.get_meta(
                    CHUNKS_DIR, chunk, meta_name="chunk.json"
                )
                is not None
            )
        except (OSError, ConnectionError):
            return False

    def _next_assignment(self):
        """The oldest undelivered assignment addressed to this worker
        (highest attempt per chunk wins — an older attempt addressed
        here may have been superseded by a re-dispatch elsewhere)."""
        best: Dict[str, str] = {}
        for entry in self._scan_assignments():
            chunk, attempt = entry.rsplit("_a", 1)
            prev = best.get(chunk)
            if prev is None or int(attempt) > int(prev.rsplit("_a", 1)[1]):
                best[chunk] = entry
        for chunk in sorted(best):
            entry = best[chunk]
            try:
                # route on the meta alone — N idle workers polling every
                # fraction of a second must not each load every
                # in-flight assignment's full prompt arrays off the
                # transport
                meta = self.transport.get_meta(
                    DISPATCH_DIR, entry, meta_name="assignment.json"
                )
                if meta is None or meta.get("worker") != self.worker_id:
                    continue
                msg = self.transport.get(
                    DISPATCH_DIR, entry, meta_name="assignment.json"
                )
            except (OSError, ConnectionError):
                # transient transport outage (tcp hub restart, shared-fs
                # hiccup): the next poll tick retries — a worker must
                # not die for a blip the scan path already tolerates
                return None
            if msg is not None:
                return msg
        return None

    # -- production -------------------------------------------------------

    def _produce(self, meta: Dict[str, Any], arrays) -> None:
        from trlx_tpu.utils import Clock

        t = self.trainer
        chunk_id = tuple(meta["chunk_id"])
        iter_count = int(meta.get("iter_count", 0))
        if t.chaos is not None and t.chaos.consult("fleet_partition"):
            # chaos: network partition — the worker is alive but its
            # beats can't land; the learner must evict + re-dispatch,
            # and this worker's late delivery must dedup away (or land
            # first — bit-identical either way)
            self._beat_pause.set()
            time.sleep(t.chaos.stall_delay)
            self._beat_pause.clear()
        self._refresh_weights()
        snap = serde.snapshot_from_wire(meta["snapshot"], t.rng)
        t._exp_restore_snapshot(snap)
        batch = serde.prompt_batch_from_arrays(
            arrays, meta.get("prompt_metadata")
        )

        def die_mid_chunk() -> None:
            if t.chaos is not None and t.chaos.consult("fleet_worker_death"):
                # chaos: the worker dies MID-CHUNK (generation done,
                # score pending) — a hard exit, so the beat thread dies
                # with it and the learner sees exactly what a real kill
                # looks like
                logger.error(
                    "chaos: fleet worker %r dying mid-chunk %s",
                    self.worker_id, chunk_id,
                )
                os._exit(3)

        (rollout_batch, stats, rows_local), _ = t._produce_chunk(
            iter_count, Clock(), batch=batch, after_generate=die_mid_chunk
        )
        try:
            delivered = self.transport.put(
                CHUNKS_DIR,
                f"e{chunk_id[0]}_s{chunk_id[1]}",
                {
                    "chunk_id": list(chunk_id),
                    "policy_version": int(self._held_version or 0),
                    "stats": serde.stats_to_wire(stats),
                    "rows_local": int(rows_local),
                    "post_snapshot": serde.snapshot_to_wire(
                        t._exp_snapshot()
                    ),
                    "worker": self.worker_id,
                    "attempt": int(meta.get("attempt", 1)),
                },
                serde.rollout_to_arrays(rollout_batch),
                meta_name="chunk.json",
            )
        except (OSError, ConnectionError) as e:
            # delivery lost to a partition/hub restart: the attempt is
            # NOT marked done, so the next poll re-produces this exact
            # assignment — bit-identical by the replay contract — and
            # re-posts through the dedup
            logger.warning(
                "fleet worker %r: delivery of chunk %s failed (%s); "
                "will regenerate and re-post", self.worker_id, chunk_id, e,
            )
            return
        self._done.add(
            f"e{chunk_id[0]}_s{chunk_id[1]}_a{int(meta.get('attempt', 1))}"
        )
        self._produced += 1
        logger.info(
            "fleet worker %r: chunk %s %s", self.worker_id, chunk_id,
            "delivered" if delivered else
            "already delivered elsewhere (dropped as duplicate)",
        )

    # -- the loop ---------------------------------------------------------

    def run(self) -> int:
        deadline = time.time() + self.cfg.attach_timeout_s
        while not self._sync_membership():
            if shutdown_requested(self.transport):
                return 0
            if time.time() >= deadline:
                logger.error(
                    "fleet worker %r: no learner attached within "
                    "attach_timeout_s=%g — giving up", self.worker_id,
                    self.cfg.attach_timeout_s,
                )
                return 1
            time.sleep(self.cfg.poll_s)
        beat_thread = threading.Thread(
            target=self._beat_loop, name="fleet-beat", daemon=True
        )
        beat_thread.start()
        last_attached = time.time()
        try:
            while True:
                if shutdown_requested(self.transport):
                    logger.info(
                        "fleet worker %r: learner signalled shutdown "
                        "after %d chunks", self.worker_id, self._produced,
                    )
                    return 0
                if self._sync_membership():
                    last_attached = time.time()
                elif (
                    time.time() - last_attached
                    >= self.cfg.detach_timeout_s
                ):
                    # the control plane has been GONE (membership
                    # unreadable/absent) for the whole window: a
                    # learner restart or hub relaunch would have
                    # re-registered us long ago. The likeliest story
                    # is a learner that finished and closed its hosted
                    # hub while our link was partitioned — its
                    # shutdown flag died with the hub — so exit CLEAN:
                    # the delivered chunks are this worker's durable
                    # output either way
                    logger.warning(
                        "fleet worker %r: control plane unreachable "
                        "for detach_timeout_s=%g after %d chunks — "
                        "assuming the learner is gone; exiting clean",
                        self.worker_id, self.cfg.detach_timeout_s,
                        self._produced,
                    )
                    return 0
                assignment = self._next_assignment()
                if assignment is None:
                    time.sleep(self.cfg.poll_s)
                    continue
                self._produce(*assignment)
                if (
                    self.max_chunks is not None
                    and self._produced >= self.max_chunks
                ):
                    logger.info(
                        "fleet worker %r: max_chunks=%d reached",
                        self.worker_id, self.max_chunks,
                    )
                    return 0
        finally:
            self._beat_stop.set()
            beat_thread.join(timeout=2.0)


def run_worker(
    config,
    reward_fn,
    fleet_dir: Optional[str] = None,
    worker_id: Optional[str] = None,
    stop_sequences: Optional[List[str]] = None,
    max_chunks: Optional[int] = None,
) -> int:
    """Build a worker-side trainer from the learner's config and serve
    the fleet until shutdown. Returns a process exit code (0 = clean).

    The tracker is forced off (two processes must not interleave one
    metrics.jsonl) and nothing is ever checkpointed from a worker —
    its durable state is exactly the chunks it delivers.
    """
    from trlx_tpu.parallel import multihost as mh
    from trlx_tpu.utils import set_seed
    from trlx_tpu.utils.loading import get_trainer

    if mh.process_count() > 1:
        raise NotImplementedError(
            "fleet workers are single-process (one worker = one "
            "inference replica); run one worker per host instead"
        )
    fleet_cfg = FleetConfig.from_dict(getattr(config.method, "fleet", None))
    root = fleet_dir or fleet_cfg.resolved_dir(config.train.checkpoint_dir)
    # same seed => same random-init base/reference params as the
    # learner's; the policy side is replaced by the broadcast anyway
    set_seed(config.train.seed)
    # the worker-side trainer must not ATTACH as a learner (no
    # membership-epoch bump, no watchdog monitor thread, no tracker
    # file racing the learner's)
    config = config.evolve(
        train=dict(tracker=None, watchdog=dict(enabled=False)),
        method=dict(fleet=dict(enabled=False)),
    )
    trainer = get_trainer(config.train.trainer)(
        config=config, reward_fn=reward_fn,
        stop_sequences=stop_sequences or [],
    )
    worker = FleetWorker(
        trainer, root, fleet_cfg, worker_id=worker_id,
        max_chunks=max_chunks,
    )
    logger.info(
        "fleet worker %r serving %s", worker.worker_id, root,
    )
    return worker.run()
