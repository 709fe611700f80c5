"""Fault-tolerant disaggregated rollout fleet (ROADMAP item 1's
remote-producer half, on the PR 7 experience-transport substrate).

  config.py       parsed ``ppo.fleet.*`` (default off; requires
                  ``ppo.exp.enabled``).
  membership.py   worker registry: heartbeat-leased records, membership
                  epochs (learner attach/re-attach handshake), eviction
                  of silent workers, flap quarantine with doubling
                  backoff.
  broadcast.py    versioned weight broadcast: atomic snapshot publish
                  with per-file sha256 manifests; workers verify before
                  adopting and KEEP the previous version on corruption
                  (broadcast failure degrades to off-policy data the
                  ``exp.staleness`` gate corrects).
  coordinator.py  learner side: chunk dispatch/collect, worker-level
                  TTL watching, re-dispatch with the replay snapshot
                  (bit-identical regeneration), degraded-mode verdicts
                  (below ``fleet.min_workers`` -> the ``fleet``
                  guardrail signal + in-process fallback).
  dispatch.py     learner side, the protocol over those primitives
                  (``ChunkDispatcher``): one leased chunk produced on
                  the fleet — publish-if-due, readiness, selection,
                  attempts, eviction, deadline, degrade, adoption —
                  called by the transport's producer
                  (``exp/rollout.py``).
  worker.py       the cross-process rollout worker (``run_worker``):
                  a learner-less PPO trainer driven by dispatch
                  messages, sharing ``_produce_chunk`` verbatim.
  serde.py        exact pytree <-> numpy wire conversions + atomic
                  message-directory commits.

``membership``/``broadcast``/``config`` are jax-free host modules;
import ``coordinator``/``dispatch``/``worker``/``serde`` directly where
needed.
"""

from trlx_tpu.fleet.broadcast import BroadcastCorrupt, WeightBroadcast
from trlx_tpu.fleet.config import FleetConfig
from trlx_tpu.fleet.membership import WorkerRegistry

__all__ = [
    "BroadcastCorrupt",
    "FleetConfig",
    "WeightBroadcast",
    "WorkerRegistry",
]
