"""Top-level training configuration.

Parity: /root/reference/trlx/data/configs.py:10-335 — same six sections
(method/model/optimizer/scheduler/tokenizer/train), same field names, same
YAML / dict round-trip, `evolve()` deep-merge and dotted-path `update()`
semantics — reimplemented generically over a section table.

TPU-specific additions live in TrainConfig (mesh shape / sharding axes):
the reference splits parallelism across two backends (Accelerate vs NeMo,
SURVEY.md §2.4/2.6); here parallelism is config, not code.
"""

from __future__ import annotations

import dataclasses
import json
from copy import deepcopy
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import yaml

from trlx_tpu.data.method_configs import MethodConfig, get_method


def _deep_merge(base: Dict, update: Dict) -> Dict:
    """Return a new dict: `base` recursively overridden by `update`."""
    out = deepcopy(base)
    for key, val in update.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = _deep_merge(out[key], val)
        else:
            out[key] = val
    return out


def _unflatten(config: Dict[str, Any]) -> Dict[str, Any]:
    """Expand dotted keys: {"a.b.c": 1} -> {"a": {"b": {"c": 1}}}."""
    nested: Dict[str, Any] = {}
    for name, value in config.items():
        node = nested
        *path, leaf = name.split(".")
        for part in path:
            node = node.setdefault(part, {})
        if isinstance(value, dict) and not path:
            node[leaf] = _deep_merge(node.get(leaf, {}), value)
        else:
            node[leaf] = value
    return nested


class _Section:
    """Shared from_dict/to_dict for config sections with unknown-key checks."""

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(config) - known
        if unknown:
            raise ValueError(f"{cls.__name__}: unknown keys {sorted(unknown)}")
        return cls(**config)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class ModelConfig(_Section):
    """Model selection (parity: reference configs.py:37-72).

    model_path: HF-layout local directory (or name; hub access is optional),
    model_arch_type: "causal" | "seq2seq",
    num_layers_unfrozen: -1 trains all layers; k>0 trains only the top k and
      enables the in-process frozen reference branch (hydra) for PPO.
    """

    model_path: str
    model_arch_type: str = "causal"
    num_layers_unfrozen: int = -1
    peft_config: Any = None
    model_extra_configs: Dict[str, Any] = field(default_factory=dict)


@dataclass
class TokenizerConfig(_Section):
    """Tokenizer selection (parity: reference configs.py:75-97)."""

    tokenizer_path: str
    padding_side: str = "left"
    truncation_side: str = "right"
    tokenizer_extra_configs: Dict[str, Any] = field(default_factory=dict)


@dataclass
class OptimizerConfig(_Section):
    """Optimizer name + kwargs, resolved via trlx_tpu.utils registry."""

    name: str
    kwargs: Dict[str, Any] = field(default_factory=dict)


@dataclass
class SchedulerConfig(_Section):
    """LR schedule name + kwargs, resolved via trlx_tpu.utils registry."""

    name: str
    kwargs: Dict[str, Any] = field(default_factory=dict)


@dataclass
class TrainConfig(_Section):
    """Training-loop settings (parity: reference configs.py:140-236) plus
    TPU mesh fields (`mesh`, `sharding`) replacing the reference's
    accelerate/deepspeed YAML + NeMo OmegaConf split."""

    total_steps: int
    seq_length: int
    epochs: int
    batch_size: int

    # steps between checkpoints; the last step always commits one.
    # 0 = no step checkpoint at all, the final one included
    checkpoint_interval: int
    eval_interval: int

    pipeline: str
    trainer: str
    trainer_kwargs: Dict[str, Any] = field(default_factory=dict)

    project_name: str = "trlx_tpu"
    run_name: Optional[str] = None
    entity_name: Optional[str] = None
    group_name: Optional[str] = None

    checkpoint_dir: str = "ckpts"
    rollout_logging_dir: Optional[str] = None
    save_best: bool = True
    save_optimizer: bool = True
    # A checkpoint directory to restore full training state from, or
    # "auto": discover the newest COMMITted checkpoint_* under
    # checkpoint_dir and resume it (fresh start, with a logged warning,
    # when none exists). Resume continues from the saved iter_count /
    # best_reward / PRNG key / data cursor — it does not replay from 0.
    resume_from_checkpoint: Optional[str] = None
    # Retention: keep only the newest N committed checkpoint_* dirs
    # (best_checkpoint always survives). None keeps everything.
    keep_last_n: Optional[int] = None

    tracker: Optional[str] = "tensorboard"
    logging_dir: Optional[str] = None
    tags: List[str] = field(default_factory=list)

    seed: int = 1000

    minibatch_size: Optional[int] = None

    # --- TPU-native additions -------------------------------------------
    # Mesh axis sizes; any axis set to -1 absorbs the remaining devices.
    # dp: data parallel, fsdp: param/opt-state sharded data parallel
    # (ZeRO-3 parity), tp: tensor parallel (Megatron parity), sp: sequence
    # (context) parallel for long sequences (ring attention), pp: pipeline
    # parallel (GPipe microbatching over the stacked layer axis; mutually
    # exclusive with sp).
    mesh: Dict[str, int] = field(default_factory=lambda: {"dp": -1, "fsdp": 1, "tp": 1, "sp": 1})
    # Precision of params/compute; optimizer state stays fp32.
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # Rematerialization policy for transformer blocks (NeMo activation-
    # checkpointing granularity parity — megatron_20b.yaml:76-80):
    # "none" | "full" (= "save_nothing": keep layer boundaries only) |
    # "dots_saveable" (keep matmul outputs, recompute elementwise —
    # NeMo "selective") | "dots_with_no_batch_dims" (keep weight-
    # stationary matmul results only) | "offload" (same, saved to
    # pinned host memory) | "save_attn" (full recompute except the
    # pallas attention kernel's named residuals, aimed at long
    # context). See trlx_tpu/ops/remat.py.
    remat_policy: str = "none"
    # When > 0, trainer losses compute per-token logprobs / cross-entropy
    # from hidden states in this many sequence chunks under
    # jax.checkpoint (ops.common.chunked_logprobs) instead of
    # materializing the full [batch, seq, vocab] fp32 logits — at
    # b8/seq2048/vocab50257 that single tensor is 3.3 GB per
    # materialization, the difference between billion-parameter training
    # fitting one 16 GB chip or not. 0 = off. The at-scale recipe
    # (configs/mesh/single_chip_1p3b.yml) uses 8.
    logit_chunks: int = 0
    # When set (e.g. "bfloat16"), losses are differentiated through a
    # grads_dtype view of the params, so the gradient tree rides in that
    # dtype (half the HBM of fp32 grads at 1.3B: 2.6 GB vs 5.3 GB).
    # Params and optimizer masters stay `param_dtype`; with
    # minibatch accumulation the running sum stays fp32.
    grads_dtype: Optional[str] = None
    # The train step fuses forward+backward+update under one jit, so only
    # `time/step` can be reported per-step. Enabling this measures a
    # forward-only pass once (shapes are static, so its cost is constant)
    # and emits `time/forward` = that measurement and `time/backward` =
    # step - forward, matching the reference's metric keys.
    timing_split: bool = False
    # --- fault tolerance ------------------------------------------------
    # Non-finite (NaN/inf) loss or grads: commit the PRE-update
    # params/opt_state instead of the poisoned update (a traced select
    # inside the jitted step — the buffers are donated, so the host
    # could not roll back). With the fused 8-bit optimizer the guard
    # zeroes the gradients before the apply instead, so a poisoned step
    # degrades to a weight-decay-only update (docs/api.md).
    skip_nan_updates: bool = True
    # Abort the run after this many CONSECUTIVE skipped (non-finite)
    # steps: persistent NaN means diverged state, not a transient.
    max_bad_steps: int = 3
    # Retry budget (re-tries after the first attempt) for the two
    # external calls in the loop — tracker.log and the reward function —
    # with exponential backoff from retry_base_delay (doubling, capped,
    # jittered). A tracker that stays down degrades to a logged error;
    # a reward function that stays down fails the run.
    external_retries: int = 3
    retry_base_delay: float = 0.5
    # Run ALL inner-epoch optimizer steps as one jitted lax.scan over
    # minibatch permutations instead of one dispatch per minibatch
    # (trainers that hold the epoch's data as a rectangular batch — PPO's
    # rollout store — support this; others fall back to the per-step
    # loop). Removes per-step dispatch latency and host syncs; per-step
    # metric granularity collapses to per-block means. The scanned path
    # draws its shuffles from the same seed stream as the looped
    # dataloaders, so it is numerically equivalent step-for-step
    # (tests/test_scanned_epochs.py); checkpoint/eval cadence quantizes
    # to block boundaries when the intervals don't divide the block.
    # Default ON since the dispatch-free-cycle change; set False for
    # exact per-step cadence/metrics.
    fused_inner_loop: bool = True
    # Defer fused-block metrics behind an async device->host copy and
    # consume them one cycle later (next block start / learn() exit):
    # the host never blocks on the device between cycle boundaries, so
    # per-block `jax.block_until_ready`-style fetches (a device sync
    # each) disappear from the steady-state loop. Checkpoint/eval
    # boundary blocks still flush synchronously (those operations
    # block on the device anyway), and
    # the NaN-abort guard then fires at most one cycle late. False
    # restores the immediate per-block fetch.
    async_metrics: bool = True
    # --- run guardrails (divergence watchdog) ---------------------------
    # Parsed by utils/guardrails.GuardrailConfig (enabled/window/
    # loss_spike_sigma/kl_factor/reward_sigma/grad_norm_max/
    # cycle_time_factor/consistency_every/consistency_atol/ladder/
    # lr_cut_factor/cooldown_cycles/max_rollbacks/recover_after).
    # consistency_every > 0 arms the cross-host consistency watchdog:
    # a cheap param/opt-state fingerprint is allgather-compared every N
    # cycles (multihost.consensus) and a disagreeing host trips the
    # ladder. Default {} = disabled: identical
    # behavior to pre-guardrails builds. When enabled, health trips walk
    # the escalation ladder (log -> requeue -> lr_cut -> rollback ->
    # abort), checkpoint commits are gated on health, and auto-rollback
    # restores the last good checkpoint. See docs/robustness.md.
    guardrails: Dict[str, Any] = field(default_factory=dict)
    # --- resilient external I/O -----------------------------------------
    # Parsed by utils/resilient.ResilientIOConfig (reward_timeout/
    # retries/base_delay/max_delay/jitter/breaker_threshold/
    # breaker_reset_s/fallback_reward). Default {} keeps PR 1 semantics:
    # plain retry+backoff, reward failures propagate. Setting
    # fallback_reward ("hold_mean" or a number) arms the circuit
    # breaker and degrades a dead reward service to the fallback instead
    # of failing the run; reward_timeout bounds each attempt.
    resilient_io: Dict[str, Any] = field(default_factory=dict)
    # --- elastic recovery (topology-change resume + ckpt integrity) ----
    # Parsed by utils/checkpointing.ElasticConfig (integrity/
    # verify_integrity/allow_topology_change). Defaults (all true):
    # every checkpoint commit includes a per-file sha256 manifest and a
    # topology manifest; trainer.load() verifies the hashes first and
    # QUARANTINES a mismatching checkpoint (renamed *.corrupt, never
    # deleted) — auto-resume and guardrail auto-rollback then fall back
    # to the previous committed step; and a checkpoint saved under a
    # different mesh/host-count restores onto the CURRENT mesh
    # (params/opt-state resharded, PPO prompt stream re-split). See
    # docs/robustness.md "Elastic recovery".
    elastic: Dict[str, Any] = field(default_factory=dict)
    # --- hang doctor (watchdog: phase heartbeats + stall detection) -----
    # Parsed by utils/watchdog.WatchdogConfig (enabled/default_deadline_s/
    # deadline_s (per-phase: rollout/reward/fused_block/train_step/
    # checkpoint/eval/experience)/scale_factor/min_samples/window/
    # poll_interval_s/timeline/idle_deadline_s/dump_stacks/
    # emergency_snapshot/barrier_timeout_s). Default {} = disabled (no
    # monitor thread, beats are free). When enabled, trainers heartbeat
    # at phase boundaries and a monitor thread trips when a phase goes
    # silent past its deadline (deadlines are FLOORS, auto-raised to
    # scale_factor * the observed rolling median duration so slow-but-
    # healthy CPU runs don't false-trip). On trip: all-thread stack dump
    # + phase timeline -> emergency snapshot from the host-RAM shadow of
    # the last health-gated state -> abort with the "stalled" exit class
    # (watchdog.EXIT_STALLED = 87), distinguishable from a crash. See
    # docs/robustness.md "Hang doctor".
    watchdog: Dict[str, Any] = field(default_factory=dict)
    # --- memory doctor (HBM admission control + OOM recovery ladder) ----
    # Parsed by utils/memdoctor.MemoryConfig (enabled/preflight/
    # hbm_bytes/headroom/high_watermark/watermark_window/
    # sample_interval_s/ladder/pool_shrink_factor/max_pool_shrinks/
    # max_splits/remat_escalation/accept_undegrade). Default {} =
    # disabled: no preflight, no watermark sampler, RESOURCE_EXHAUSTED
    # propagates raw. When enabled: learn() first builds an analytic
    # per-phase HBM plan (params/opt/grads/activations; decode-engine
    # page pools + draft model) and REJECTS an over-budget config with
    # an itemized report before any compile; a host-side sampler feeds
    # the `memory` guardrail signal when bytes-in-use crosses the high
    # watermark; and an OOM walks the degradation ladder — shrink the
    # gen-engine page pool -> split the train microbatch (golden-equal
    # grad accumulation) -> escalate remat -> rollback to the last
    # health-gated checkpoint with the degradation PERSISTED in
    # state.json -> itemized abort. See docs/robustness.md "Memory
    # doctor".
    memory: Dict[str, Any] = field(default_factory=dict)
    # --- flight recorder / run telemetry (observability) ----------------
    # Parsed by obs.ObsConfig (enabled/dir/rotate_bytes/keep_files/
    # telemetry_window/events_tail/profile.{start_cycle,stop_cycle,
    # on_trip,dir,force}). DEFAULT ON (unlike the other subsystems —
    # the point is that every run self-documents): a span tracer rides
    # the hang doctor's existing beat sites to produce a per-cycle
    # phase wall-time breakdown (phase sum == cycle wall by
    # construction); guardrail trips, chaos injections, memdoctor
    # watermark/OOM-ladder events, fleet degradations and supervisor
    # restarts all land in ONE size-rotated JSONL flight-recorder
    # stream under <checkpoint_dir>/flight/, correlated by
    # run_id/cycle/policy_version; and a provenance-stamped
    # telemetry.json with the bench-comparable headline numbers
    # (samples/s, mask-weighted tokens/s, phase breakdown, engine
    # ledger, analytic MFU estimate) is committed alongside every
    # checkpoint. train.obs.profile.* arms an on-demand jax.profiler
    # window (cycles N..M, or one-shot on a perf/memory guardrail
    # trip). Host-side only, no device syncs; {enabled: false}
    # restores pre-obs behavior. Render with scripts/flight_report.py;
    # runbook: docs/observability.md.
    obs: Dict[str, Any] = field(default_factory=dict)
    # --- live-traffic serving tier --------------------------------------
    # Parsed by serve.config.ServeConfig (enabled/max_batch/slots/
    # page_size/pool_pages/max_prompt_len/max_new_tokens/
    # default_max_tokens/default_deadline_s/kv_quant/
    # max_batches_per_tick/starvation_report_after/prefix_cache/
    # sessions/session_deadline_s/max_cache_entries/transport/seed).
    # Default {} = disabled. When enabled, learn() hosts a serving
    # frontend on the SAME continuous-batching decode engine that
    # produces training rollouts, on the live policy params: external
    # requests (prompt, max_tokens, sampling seed-by-request-id,
    # deadline) are admitted at the lane-refill decision points with
    # SLO scheduling (EDF; serving outranks training refills under a
    # bounded per-tick allowance; deadline-expired requests are evicted
    # with their pages reclaimed), a refcounted prefix/session KV cache
    # shares page-aligned system prompts across requests and pins
    # multi-turn sessions, and requests arrive over a pluggable
    # transport (shared_fs under <checkpoint_dir>/serve, or a tcp hub).
    # The training loss stream stays bit-equal to a no-serving run by
    # construction. See docs/serving.md.
    serve: Dict[str, Any] = field(default_factory=dict)
    # --- chaos injection (tests/CI only) --------------------------------
    # Parsed by utils/chaos.ChaosMonkey: {"seed": int, "faults": [
    # {"fault": "nan_loss"|"sigterm"|"nan_reward"|"reward_timeout"|
    # "reward_error"|"ckpt_fail"|"ckpt_corrupt"|"host_divergence"|
    # "stall_rollout"|"stall_reward"|"stall_collective"|
    # "worker_death_mid_lease"|"duplicate_delivery"|"stale_flood"|
    # "queue_wedge"|"fleet_worker_death"|"fleet_partition"|
    # "broadcast_corrupt"|"oom_fused_block"|"oom_prefill"|"hbm_creep"|
    # "serve_request_timeout"|"serve_lane_starvation"|
    # "serve_transport_drop",
    # "at": k | "every": n | "p": x,
    # "span": m}], "reward_delay": s, "stall_delay": s}. None/{}
    # disables. Deterministic given the seed — see docs/robustness.md
    # for the schedule format (the stall_* sites sleep stall_delay
    # seconds to prove the hang doctor end to end; the oom_* sites
    # raise simulated RESOURCE_EXHAUSTED for the memory doctor's
    # ladder, hbm_creep saturates its watermark sampler).
    chaos: Optional[Dict[str, Any]] = None


_SECTIONS: Tuple[Tuple[str, type], ...] = (
    ("model", ModelConfig),
    ("tokenizer", TokenizerConfig),
    ("optimizer", OptimizerConfig),
    ("scheduler", SchedulerConfig),
    ("train", TrainConfig),
)


@dataclass
class TRLConfig:
    """Top-level config (parity: reference configs.py:239-335)."""

    method: MethodConfig
    model: ModelConfig
    optimizer: OptimizerConfig
    scheduler: SchedulerConfig
    tokenizer: TokenizerConfig
    train: TrainConfig

    @classmethod
    def load_yaml(cls, yml_fp: str) -> "TRLConfig":
        with open(yml_fp) as f:
            return cls.from_dict(yaml.safe_load(f))

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "TRLConfig":
        sections = {name: sec.from_dict(config[name]) for name, sec in _SECTIONS}
        method_cls = get_method(config["method"]["name"])
        return cls(method=method_cls.from_dict(config["method"]), **sections)

    def to_dict(self) -> Dict[str, Any]:
        data = {name: getattr(self, name).to_dict() for name, _ in _SECTIONS}
        data["method"] = self.method.to_dict()
        return data

    def evolve(self, **kwargs) -> "TRLConfig":
        """Deep-merge keyword overrides, returning a new config.

        >>> cfg.evolve(method=dict(gamma=0.99), train=dict(seed=7))
        """
        return TRLConfig.from_dict(_deep_merge(self.to_dict(), kwargs))

    @classmethod
    def update(cls, baseconfig, config: Dict[str, Any]) -> "TRLConfig":
        """Apply dotted-path overrides ("train.seed": 1) with validation that
        every override path exists in the base (sweep-tool contract,
        reference configs.py:303-329)."""
        if not isinstance(baseconfig, dict):
            baseconfig = baseconfig.to_dict()
        overrides = _unflatten(config)

        def _check(base, upd, path=""):
            for k, v in upd.items():
                if k not in base:
                    raise ValueError(f"parameter {path}{k} is not present in the config")
                if isinstance(v, dict) and isinstance(base[k], dict):
                    _check(base[k], v, f"{path}{k}.")

        _check(baseconfig, overrides)
        return cls.from_dict(_deep_merge(baseconfig, overrides))

    def __str__(self) -> str:
        return json.dumps(self.to_dict(), indent=4)
