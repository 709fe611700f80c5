"""Resilient experience transport: the substrate for disaggregated
async actor–learner training (ROADMAP item 1, IMPACT/OPPO in PAPERS.md).

Rollout producers and the learner stop sharing one lockstep loop here:
experience travels through a durable, sharded queue with at-least-once
delivery and lease-based production, so the failure semantics of the
experience path — a worker dying mid-chunk, duplicate delivery on
retry, stale batches corrupting the PPO surrogate — are owned by one
chaos-proven layer instead of leaking into every trainer.

  queue.py      bounded FIFO of experience chunks keyed by a
                monotonically increasing ``(epoch, chunk_seq)`` id, with
                consumer-side dedup (redelivered ids dropped), in-order
                consumption, back-pressure past ``exp.max_depth``, and a
                persisted consumer cursor (committed inside the atomic
                checkpoint via the trainer's ``state.json``). Also the
                staleness admission gate (``exp.staleness.mode:
                reject|clip``) and the parsed ``ppo.exp`` config.
  leases.py     per-chunk production leases with watchdog-style
                heartbeats; an expired lease (worker death, stall) is
                reclaimed and its chunk re-dispatched to a live
                producer.
  transport.py  the orchestrator the trainers drive: produce-side
                ``begin_chunk``/``deliver`` (lease + back-pressure),
                consume-side ``poll``/``admit``/``committed`` (dedup +
                staleness), epoch aborts for guardrail requeue/rollback,
                and ``state_dict``/``load_state_dict`` for resume.
  rollout.py    the rollout loop's side: ``LeasedChunks``, the chunk
                source an online trainer's collection loop pulls from —
                the in-process consumer and producer over
                ``transport.py`` (replay snapshots, heartbeats, the
                ``exp_wait`` phase, reclaim, the staleness verdicts and
                re-dispatch), given the trainer's functions as
                arguments.
  net.py        the PROCESS-BOUNDARY substrate: the pluggable topic/
                message transport (atomic-rename shared-fs, or a tcp
                hub) that carries fleet chunk dispatch/delivery and
                the serving tier's request/response traffic across
                machines. ``transport.py`` is the delivery state
                machine; ``net.py`` is the wire it can ride.

Everything here is pure host-side bookkeeping — no jax at module scope
— with injectable clocks, so tier-1 tests cover every delivery
interleaving on a fake clock (tests/test_exp_queue.py).
"""

from trlx_tpu.exp.leases import Lease, LeaseTable
from trlx_tpu.exp.queue import (
    ExpConfig,
    ExperienceChunk,
    ExperienceQueue,
    StalenessConfig,
)
from trlx_tpu.exp.transport import ExperienceTransport

__all__ = [
    "ExpConfig",
    "ExperienceChunk",
    "ExperienceQueue",
    "ExperienceTransport",
    "Lease",
    "LeaseTable",
    "StalenessConfig",
]
