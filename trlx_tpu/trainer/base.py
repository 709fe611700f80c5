"""TPU trainer base: model/optimizer setup, generation, evaluation, the
`learn()` loop, and checkpointing.

Parity: /root/reference/trlx/trainer/accelerate_base_trainer.py:40-682
(AccelerateRLTrainer) — same hook surface (`get_arch` / `loss` /
`prepare_learning` / `create_train_dataloader` / `post_backward_callback`
/ `post_epoch_callback`), the same loop structure (epochs -> inner epochs
-> batches with gradient accumulation), the same checkpoint layout
(`checkpoint_{step}` + `best_checkpoint`, each containing `hf_model/`)
and the same metric keys (`time/step`, `reward/mean`,
`learning_rate_group_0`, ...; `time/forward`/`time/backward` are emitted
when `train.timing_split` is on — the fused jitted step has no per-step
split, so those keys come from a one-shot measured forward probe).

TPU re-design:
- One trainer covers what the reference splits across the Accelerate and
  NeMo backends: DP/FSDP/TP are mesh-axis sizes in `TrainConfig.mesh`.
- Gradient accumulation is a `lax.scan` over microbatches inside ONE
  jitted train step (the reference's `_accumulate`/no_sync dance exists
  to suppress per-microbatch NCCL allreduce — under jit the grads are
  reduced exactly once by construction).
- The optimizer step, freeze masking and LR schedule live in the same
  jitted function; params/opt-state are donated (no HBM copies).
"""

from __future__ import annotations

import dataclasses
import json
import os
from abc import abstractmethod
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.generation import (
    HF_GEN_KWARGS_UNIMPLEMENTED,
    SamplerSettings,
    fused_decode_cells,
    generate,
    state_bytes_per_step,
)
from trlx_tpu.models.hf import load_pretrained, save_pretrained_hf
from trlx_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    decode_weights_stationary,
    state_step_unfused,
)
from trlx_tpu.ops import trainable_view
from trlx_tpu.ops.common import head_gathered_once
from trlx_tpu.parallel import (
    data_sharding,
    init_sharded_opt_state,
    make_mesh,
)
from trlx_tpu.parallel import multihost as mh
from trlx_tpu.parallel.mesh import replicated_sharding, vector_sharding
from trlx_tpu.trainer import BaseRLTrainer
from trlx_tpu.utils import (
    Clock,
    build_optimizer,
    logging,
    significant,
    to_scalar,
)
from trlx_tpu.utils.chaos import build_chaos, poison_batch
from trlx_tpu.utils.checkpointing import (
    TOPOLOGY_MANIFEST,
    CheckpointCorruptError,
    CheckpointManager,
    ElasticConfig,
    PreemptionHandler,
    atomic_json_write,
    verify_or_quarantine,
)
from trlx_tpu.utils.guardrails import (
    MEMORY_SIGNAL,
    STALL_SIGNAL,
    build_monitor,
)
from trlx_tpu.obs import build_observer
from trlx_tpu.obs.telemetry import tree_param_count
from trlx_tpu.utils.memdoctor import (
    MemoryAbortError,
    MemoryPlanError,
    build_memdoctor,
    classify_oom,
    estimate_plan,
    is_degraded_record,
    is_oom,
    remat_strength,
)
from trlx_tpu.utils.resilient import (
    ChaosFault,
    CircuitBreaker,
    ResilientCaller,
    ResilientIOConfig,
    retry_call,
)
from trlx_tpu.utils.tokenizers import load_tokenizer
from trlx_tpu.utils.trackers import DeferredStats, Tracker
from trlx_tpu.utils.watchdog import StallReport, build_watchdog

logger = logging.get_logger(__name__)

# TransformerConfig knobs that tune EXECUTION, not architecture. Mesh
# presets ship these in model_extra_configs["transformer"]; they apply
# on top of whatever checkpoint is loaded, and their presence alone
# must not trigger the random-init path (architecture keys do).
_RUNTIME_TRANSFORMER_KEYS = frozenset({
    "attention_impl", "kv_cache_quant", "decode_weights_quant",
    "pp_microbatches", "pp_schedule",
})


def _apply_runtime_overrides(cfg, extra_dict):
    """Apply _RUNTIME_TRANSFORMER_KEYS present in a model_extra_configs
    sub-dict onto a loaded model config (only the fields the config
    actually has — seq2seq has decode_weights_quant but not
    kv_cache_quant, for instance)."""
    names = {f.name for f in dataclasses.fields(cfg)}
    ov = {
        k: v
        for k, v in extra_dict.items()
        if k in _RUNTIME_TRANSFORMER_KEYS and k in names
    }
    return cfg.replace(**ov) if ov else cfg

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def _batch_shape_key(device_batch) -> tuple:
    """Hashable leaf-shape signature of a batch pytree; keys both the
    timing-split probe cache and the compile-step gate."""
    return tuple(tuple(x.shape) for x in jax.tree_util.tree_leaves(device_batch))


class TPUBaseTrainer(BaseRLTrainer):
    """Shared trainer machinery; subclasses provide the algorithm."""

    def __init__(
        self,
        config: TRLConfig,
        reward_fn: Optional[Callable] = None,
        metric_fn: Optional[Callable] = None,
        stop_sequences: Optional[List[str]] = None,
        **kwargs: Any,
    ):
        super().__init__(config, reward_fn, metric_fn, stop_sequences)
        train = config.train
        self.mesh = make_mesh(train.mesh)
        if mh.is_multihost():
            # validates the process->row-block mapping up front (raises on
            # layouts where batch rows can't be distributed consistently,
            # e.g. a process straddling partial data shards) and warms the
            # data-group cache: with pp>1 spanning processes, stages are
            # REPLICAS of the same rows and every row helper keys on data
            # groups, not processes
            mh.data_group_info(self.mesh)
        self.compute_dtype = _DTYPES[train.compute_dtype]
        self.param_dtype = _DTYPES[train.param_dtype]
        self.tokenizer = load_tokenizer(config.tokenizer)
        self.rng = jax.random.PRNGKey(train.seed)

        # run guardrails (divergence watchdog) + chaos harness +
        # resilient reward I/O — all default-off / behavior-preserving
        self.guardrails = build_monitor(train)
        self.chaos = build_chaos(train)
        # hang doctor: phase heartbeats + stall monitor thread (armed
        # for the duration of learn(); default-off = free beats, no
        # thread). Escalation on trip: guardrails `stall` record ->
        # emergency snapshot from the host-RAM shadow -> stalled abort.
        self.watchdog = build_watchdog(train)
        self.watchdog.on_stall(self._on_watchdog_stall)
        # flight recorder (train.obs.*, trlx_tpu/obs/ — DEFAULT ON):
        # span tracer riding the watchdog's beat sites, unified JSONL
        # event stream under <checkpoint_dir>/flight/ fed by the
        # guardrail/chaos listeners registered here, and continuous
        # bench-comparable telemetry committed with every checkpoint.
        # Host-side only; never raises into the loop. Built (from the
        # config alone, with the three islands it attaches to) BEFORE the
        # weights, so that its spans and its compile listener see the
        # whole constructor: set-up is most of a short run's chip time.
        self.obs = build_observer(
            train,
            checkpoint_dir=train.checkpoint_dir,
            is_writer=mh.is_main(),
            watchdog=self.watchdog,
            guardrails=self.guardrails,
            chaos=self.chaos,
        )

        # subclass hook: builds self.model (wrapper), self.params and any
        # auxiliary trees (e.g. PPO's frozen reference branch)
        with self.obs.span("model_init") as counts:
            self.setup_model()
            counts["params"] = tree_param_count(getattr(self, "params", None))
        # the model itself must know a mesh of more than one device:
        # context parallelism (ring attention over `sp`) and pipeline
        # parallelism (layer stack over `pp`) run teacher-forced forwards
        # through shard_map, so do the pallas kernels (GSPMD cannot
        # partition a Mosaic call), and a decode step lays its
        # activations out by the `fsdp` axis (parallel/sharding.py
        # `DecodeLayouts`)
        if self.mesh.size > 1:
            self._lm().mesh = self.mesh

        self._update_mask = self.trainable_mask()
        # static, from the mask's values and the shapes alone: what of each
        # leaf an optimizer step walks
        self._view = trainable_view.trainable_view(self._update_mask, self.params)
        with self.obs.span("opt_init"):
            self.tx, self.schedule = self._assemble_optimizer(
                config.optimizer, config.scheduler
            )
            with self.mesh:
                self.opt_state = init_sharded_opt_state(self.mesh, self.tx, self.params)

        gen_kwargs = dict(config.method.gen_kwargs)
        self.generate_sweep_kwarg = None
        for k, v in gen_kwargs.items():
            if isinstance(v, list):
                self.generate_sweep_kwarg = (k, v)
        if self.generate_sweep_kwarg:
            gen_kwargs.pop(self.generate_sweep_kwarg[0])
        eos = getattr(self.tokenizer, "eos_token_id", None)
        pad = getattr(self.tokenizer, "pad_token_id", None)
        if pad is None:  # NOT `or`: pad_token_id == 0 is legitimate (T5)
            pad = eos
        self.generate_settings = SamplerSettings.from_gen_kwargs(
            gen_kwargs, eos_token_id=eos, pad_token_id=pad
        )
        exp_kwargs = getattr(config.method, "gen_experience_kwargs", None)
        self.generate_experience_settings = (
            SamplerSettings.from_gen_kwargs(exp_kwargs, eos_token_id=eos, pad_token_id=pad)
            if exp_kwargs
            else self.generate_settings
        )

        self.tracker = Tracker(config)
        self.iter_count = 0
        self.nth_evaluation = 0
        self.best_reward = -float("inf")
        self.total_steps = train.total_steps
        # elastic recovery: integrity manifests + topology-change resume
        self.elastic = ElasticConfig.from_dict(train.elastic)
        self.ckpt_manager = CheckpointManager(
            train.checkpoint_dir, keep_last_n=train.keep_last_n,
            integrity=self.elastic.integrity,
        )
        self.preemption = PreemptionHandler()
        self._bad_steps = 0  # consecutive non-finite-loss steps
        self._preempt_sync_counter = 0  # multihost any_flag cadence
        # tracker outage circuit: open after _TRACKER_CIRCUIT_LIMIT
        # consecutive exhausted-retry failures; reset_timeout=0 allows
        # one un-retried probe per step while open
        self._tracker_breaker = CircuitBreaker(
            failure_threshold=self._TRACKER_CIRCUIT_LIMIT, reset_timeout=0.0
        )
        self._rollout_abandoned = False  # preemption truncated the store
        self._warned_shadow_skip = False
        # memory doctor (train.memory.*): preflight HBM admission
        # control, runtime watermark sampling (feeding the `memory`
        # guardrail signal), and the OOM recovery ladder (shrink pool
        # -> split microbatch -> remat -> rollback -> itemized abort).
        # Default-off = behavior-preserving: no preflight, no sampler
        # thread, RESOURCE_EXHAUSTED propagates raw.
        self.memdoctor = build_memdoctor(train)
        # per-phase peak attribution keys off the hang doctor's
        # heartbeat registry (enable train.watchdog for phase-resolved
        # peaks; otherwise everything lands under "run")
        self.memdoctor.sampler.set_phase_fn(self.watchdog.current_phase)
        self._hbm_plan = None  # preflight plan, kept for the abort report
        # the last cycle's async metrics must survive shutdown in any
        # order: tracker.close() drains these before backend teardown
        self.tracker.attach_pending(self._finish_rollout_stats)
        self.tracker.attach_pending(
            lambda: self._finish_train_stats(suppress_abort=True)
        )
        self._resilient_cfg = ResilientIOConfig.from_dict(train.resilient_io)
        self._reward_caller: Optional[ResilientCaller] = None  # lazy
        self._lr_scale = 1.0  # cumulative guardrail LR-cut factor
        self._ckpt_commit_failures = 0  # consecutive failed commits
        # run-derived step budget of a restored checkpoint (PPO lowers
        # total_steps from the store size inside prepare_learning, so
        # the config value alone can't tell a completed run from one
        # with steps left)
        self._restored_total_steps: Optional[int] = None
        self._restored_config_total_steps: Optional[int] = None

        mb_size = train.minibatch_size or train.batch_size
        if train.batch_size % mb_size:
            raise ValueError("batch_size must be divisible by minibatch_size")
        self.mb_size = mb_size
        self.num_mb = train.batch_size // mb_size
        data_ways = self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
        if mb_size % data_ways:
            raise ValueError(
                f"minibatch_size {mb_size} must be divisible by dp*fsdp={data_ways} "
                f"(mesh {dict(self.mesh.shape)})"
            )

        self._train_step = None  # built lazily (jitted)
        self._fused_train_step = None  # built lazily (jitted inner loop)
        self._warned_fused_cadence = False
        # fused-block metrics ride an async device->host copy and are
        # consumed one cycle later (train.async_metrics)
        self._deferred_train = DeferredStats()
        self._last_cycle_t0: Optional[float] = None  # guardrail wall signal
        self._measured_forward_times = {}  # timing_split probes by batch shape
        self._seen_step_shapes = set()  # batch shapes whose step has compiled
        self._generate_fns: Dict[Tuple, Callable] = {}
        # per built sampler: the fused decode kernel's grid, or None
        self._decode_cells: Dict[Tuple, Optional[Dict[str, int]]] = {}
        # serving-grade rollout decode engine (ppo.gen_engine.*):
        # continuous batching + paged KV + speculative decoding behind
        # the same generate() seam; default-disabled
        from trlx_tpu.models.gen_engine import GenEngineConfig

        self._engine_cfg = GenEngineConfig.from_dict(
            getattr(config.method, "gen_engine", None)
        )
        self._engine_fns: Dict[Tuple, Callable] = {}
        self._warned_engine_fallback = False
        # live-traffic serving tier (train.serve.*): external requests
        # admitted into the same continuous-batching engine on the live
        # policy params, ticked at the lane-refill decision points.
        # Default off; built lazily at learn() start (_serve_start)
        from trlx_tpu.serve.config import ServeConfig

        self._serve_cfg = ServeConfig.from_dict(
            getattr(config.train, "serve", None)
        )
        self.serve = None  # ServeFrontend while learn() runs
        self._serve_fn = None  # jitted serving engine entry
        # cross-host consistency watchdog (guardrails.consistency_every)
        self._fingerprint_fn = None  # jitted replicated state reduction
        self._consistency_counter = 0
        # policy version: optimizer CYCLES applied to the params (one
        # fused block, or one inner epoch of the per-step loop). This is
        # the experience transport's staleness unit — every chunk
        # records the version its samples were generated at, and the
        # admission gate compares it against the version at consumption
        # (the overlap_rollouts prefetch is exactly 1 stale by
        # construction).
        self._policy_version = 0

    # ------------------------------------------------------------------
    # model setup
    # ------------------------------------------------------------------

    def load_base_model(self) -> Tuple[TransformerConfig, Dict, Optional[str]]:
        """Resolve ModelConfig -> (transformer config, base params, model_type).

        `model_path="random"` (or a "transformer" dict in
        model_extra_configs) random-initializes — the zero-egress path
        used by tests and benchmarks; otherwise an HF-layout checkpoint
        directory is loaded (parity: reference modeling_base.py:124-326).
        """
        mc = self.config.model
        extra = mc.model_extra_configs or {}
        if mc.model_arch_type == "seq2seq":
            return self._load_seq2seq_base(mc, extra)

        def finalize(tcfg):
            # runtime knobs from model_extra_configs apply to EVERY load
            # path (mesh presets ship e.g. kv_cache_quant — they must
            # tune a loaded checkpoint, not reroute it to random init)
            tcfg = _apply_runtime_overrides(tcfg, extra.get("transformer", {}))
            # mesh sp>1 means the user asked for context parallelism: switch
            # the default attention to the ring implementation (an explicit
            # attention_impl, e.g. "pallas", is respected as-is)
            if self.mesh.shape["sp"] > 1 and tcfg.attention_impl == "xla":
                tcfg = tcfg.replace(attention_impl="ring")
            # a tokenizer id >= vocab_size would silently fill the embedding
            # gather with NaN under XLA (jnp.take fill mode) — fail loudly
            for name in ("pad_token_id", "eos_token_id", "bos_token_id"):
                tid = getattr(self.tokenizer, name, None)
                if tid is not None and int(tid) >= tcfg.vocab_size:
                    raise ValueError(
                        f"tokenizer {name}={tid} is out of range for model "
                        f"vocab_size={tcfg.vocab_size}; align the model's "
                        "vocab_size with the tokenizer (the byte tokenizer "
                        "needs vocab_size>=258)"
                    )
            return tcfg

        native_cfg_fp = os.path.join(mc.model_path, "trlx_tpu_config.json")
        if os.path.isdir(mc.model_path) and os.path.exists(native_cfg_fp):
            # native checkpoint (orbax params + architecture json), the
            # deploy artifact save_pretrained writes for random-init runs
            import orbax.checkpoint as ocp

            with open(native_cfg_fp) as f:
                meta = json.load(f)
            tcfg = TransformerConfig(
                dtype=self.compute_dtype, param_dtype=self.param_dtype,
                **meta["transformer"],
            )
            params = ocp.PyTreeCheckpointer().restore(
                os.path.join(os.path.abspath(mc.model_path), "params")
            )
            aux_dir = os.path.join(os.path.abspath(mc.model_path), "aux")
            if os.path.isdir(aux_dir):
                self._loaded_aux = ocp.PyTreeCheckpointer().restore(aux_dir)
            return finalize(tcfg), params, meta.get("model_type")
        # random-init only when asked by path or by ARCHITECTURE keys —
        # a preset carrying only runtime knobs (kv_cache_quant, ...)
        # must not silently replace a pretrained model with random init
        arch_keys = set(extra.get("transformer", {})) - _RUNTIME_TRANSFORMER_KEYS
        if mc.model_path == "random" or arch_keys:
            tdict = dict(extra.get("transformer", {}))
            tdict.setdefault("vocab_size", getattr(self.tokenizer, "vocab_size", 258))
            tcfg = TransformerConfig(
                dtype=self.compute_dtype, param_dtype=self.param_dtype, **tdict
            )
            self.rng, key = jax.random.split(self.rng)
            params = TransformerLM(tcfg).init(key)
            self._random_init = True
            return finalize(tcfg), params, extra.get("model_type")
        lm, params, model_type = load_pretrained(
            mc.model_path, dtype=self.compute_dtype, param_dtype=self.param_dtype
        )
        self._hf_config_path = mc.model_path
        return finalize(lm.cfg), params, model_type

    def _load_seq2seq_base(self, mc, extra):
        from trlx_tpu.models.seq2seq import Seq2SeqConfig, T5LM

        native_cfg_fp = os.path.join(mc.model_path, "trlx_tpu_config.json")
        if os.path.isdir(mc.model_path) and os.path.exists(native_cfg_fp):
            import orbax.checkpoint as ocp

            with open(native_cfg_fp) as f:
                meta = json.load(f)
            scfg = Seq2SeqConfig(
                dtype=self.compute_dtype, param_dtype=self.param_dtype,
                **meta["seq2seq"],
            )
            params = ocp.PyTreeCheckpointer().restore(
                os.path.join(os.path.abspath(mc.model_path), "params")
            )
            aux_dir = os.path.join(os.path.abspath(mc.model_path), "aux")
            if os.path.isdir(aux_dir):
                self._loaded_aux = ocp.PyTreeCheckpointer().restore(aux_dir)
            scfg = _apply_runtime_overrides(scfg, extra.get("seq2seq", {}))
            return scfg, params, meta.get("model_type", "t5")
        # same contract as the causal loader: runtime-only keys don't
        # reroute a pretrained model to random init
        if mc.model_path == "random" or (
            set(extra.get("seq2seq", {})) - _RUNTIME_TRANSFORMER_KEYS
        ):
            sdict = dict(extra.get("seq2seq", {}))
            sdict.setdefault("vocab_size", getattr(self.tokenizer, "vocab_size", 258))
            pad = getattr(self.tokenizer, "pad_token_id", None)
            if pad is not None:
                sdict.setdefault("decoder_start_token_id", int(pad))
            scfg = Seq2SeqConfig(
                dtype=self.compute_dtype, param_dtype=self.param_dtype, **sdict
            )
            self.rng, key = jax.random.split(self.rng)
            return scfg, T5LM(scfg).init(key), extra.get("model_type", "t5")
        from trlx_tpu.models.hf import load_pretrained_seq2seq

        lm, params, model_type = load_pretrained_seq2seq(
            mc.model_path, dtype=self.compute_dtype, param_dtype=self.param_dtype
        )
        self._hf_config_path = mc.model_path
        scfg = _apply_runtime_overrides(lm.cfg, extra.get("seq2seq", {}))
        return scfg, params, model_type

    @abstractmethod
    def setup_model(self) -> None:
        """Set self.model / self.params (sharded) and auxiliaries."""

    def trainable_mask(self):
        """Pytree of {0,1} update multipliers (None = all trainable).

        Freezing must mask the *updates*, not the grads: AdamW applies
        weight decay even at zero gradient (parity with
        `freeze_bottom_causal_layers`, reference
        accelerate_base_trainer.py:159-161 + utils/modeling.py:106-140).
        """
        return None

    def branch_at(self) -> Optional[int]:
        """Layer index where the trainable top starts (None = all)."""
        k = self.config.model.num_layers_unfrozen
        if k is None or k < 0:
            return None
        n_layer = self.model.cfg.n_layer
        return max(n_layer - k, 0)

    def make_freeze_mask(self, params: Dict) -> Optional[Dict]:
        """Standard causal-LM freeze mask: embeddings + bottom layers
        frozen, top-k layers + final norm + lm_head + aux heads train."""
        at = self.branch_at()
        cfg = self.model.cfg
        routed = getattr(cfg, "routed", False)
        if (at is None or at == 0) and not routed:
            return None
        from trlx_tpu.models.transformer import STACKS, TransformerConfig, stack_layers

        # a stack's rows are layers of the whole stack, not always a run of
        # them (delta-rule layers among the others): each by its own index
        rows = (
            {name: stack_layers(cfg, name) for name in STACKS}
            if isinstance(cfg, TransformerConfig) else {"blocks": range(cfg.n_layer)}
        )
        trains = {
            name: (jnp.asarray(layers, jnp.int32) >= (at or 0)).astype(jnp.float32)
            for name, layers in rows.items()
        }
        # a chip that holds a share of the experts sees the router's gradient
        # through its own experts alone, an eighth of the sum the chips of a
        # deployment would add up, and all of it pulling toward those experts:
        # the two trainable layers' held share went 1.1 -> 1.4 of uniform in 64
        # steps (chip run, PERF.md section 6). A share trains no router.
        a_share = routed and cfg.n_experts_held < cfg.n_routed_experts

        def mask_leaf(path, leaf):
            keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
            if keys[-1] == "router_bias":
                return np.float32(0.0)  # the router's selection bias is a buffer
            if a_share and keys[-1] == "router_gate":
                return np.float32(0.0)
            if "v_branch" in keys or "lora" in keys:
                return np.float32(1.0)  # branches/adapters always train
            for name, mask in trains.items():
                if name in keys:
                    return mask.reshape((-1,) + (1,) * (np.ndim(leaf) - 1))
            if "embed" in keys:  # frozen with the bottom layers, if any are
                return np.float32(0.0 if at else 1.0)
            return np.float32(1.0)

        return jax.tree_util.tree_map_with_path(mask_leaf, params)

    def attach_lora(self, params: Dict) -> Dict:
        """Back-compat alias for attach_peft."""
        return self.attach_peft(params)

    def attach_peft(self, params: Dict) -> Dict:
        """Add the configured adapter (LoRA overlay / prompt soft tokens /
        per-layer kv prefixes) to a {"base": ...} params tree.

        `ModelConfig.peft_config` is either an HF-peft-style config dict
        (fresh adapter) or a PATH to a trained HF-peft adapter checkpoint
        (adapter_config.json + adapter_model.safetensors) — both shapes
        the reference accepts (ref modeling_base.py:124-326)."""
        from trlx_tpu.models.peft import (
            init_lora_params,
            init_prefix_params,
            init_prompt_params,
            is_peft_checkpoint,
            load_peft_adapter,
            normalize_peft_config,
        )

        cfg = getattr(self.model, "cfg", None)
        if self.config.model.peft_config is not None and getattr(cfg, "beyond_dense", False):
            raise NotImplementedError(
                "peft adapters are not implemented for a model with latent attention, "
                "delta-rule (KDA) or state-space (Mamba-2) layers, routed experts or several "
                "residual streams"
            )

        if isinstance(self.config.model.peft_config, str) and not (
            is_peft_checkpoint(self.config.model.peft_config)
        ):
            raise ValueError(
                f"peft_config {self.config.model.peft_config!r} is a "
                "string but not an adapter checkpoint directory (no "
                "adapter_config.json inside); pass either a trained "
                "HF-peft adapter dir or a config dict like "
                '{"peft_type": "LORA", "r": 8}'
            )
        if is_peft_checkpoint(self.config.model.peft_config):
            pc, adapter = load_peft_adapter(
                self.config.model.peft_config, self.model.cfg
            )
            params.update(adapter)
            if pc["peft_type"] == "LORA":
                self.model.lora_scaling = pc["alpha"] / pc["r"]
            self._peft_cfg = pc
            return params
        pc = normalize_peft_config(self.config.model.peft_config)
        self._peft_cfg = pc
        if pc is None:
            return params
        self.rng, key = jax.random.split(self.rng)
        if pc["peft_type"] == "LORA":
            params["lora"] = init_lora_params(
                key, params["base"], pc["r"], pc["targets"]
            )
            self.model.lora_scaling = pc["alpha"] / pc["r"]
        elif pc["peft_type"] == "PROMPT_TUNING":
            params["prompt"] = init_prompt_params(
                key, self.model.cfg, pc["num_virtual_tokens"]
            )
        elif pc["peft_type"] == "PREFIX_TUNING":
            params["prefix"] = init_prefix_params(
                key, self.model.cfg, pc["num_virtual_tokens"]
            )
        return params

    def lora_freeze_mask(self, params: Dict) -> Optional[Dict]:
        """With any peft adapter: base frozen entirely, adapters + heads
        train (the reference peft contract)."""
        from trlx_tpu.models.peft import ADAPTER_KEYS

        if not any(k in params for k in ADAPTER_KEYS):
            return None
        mask = jax.tree_util.tree_map(lambda _: np.float32(1.0), params)
        mask["base"] = jax.tree_util.tree_map(
            lambda _: np.float32(0.0), params["base"]
        )
        return mask

    def make_seq2seq_freeze_mask(self, params: Dict) -> Optional[Dict]:
        """Seq2seq freeze: encoder + shared embedding + decoder rel-bias +
        bottom decoder layers frozen; top decoder layers, final norm,
        lm_head and aux heads train (parity: reference
        freeze_bottom_seq2seq_layers, utils/modeling.py)."""
        k = self.config.model.num_layers_unfrozen
        if k is None or k < 0:
            return None
        n_dec = self.model.cfg.n_decoder_layer
        at = max(n_dec - k, 0)
        if at == 0:
            return None
        layer_mask = (jnp.arange(n_dec) >= at).astype(jnp.float32)

        def mask_leaf(path, leaf):
            keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
            if "encoder" in keys or "shared" in keys:
                return np.float32(0.0)
            if "rel_bias" in keys:
                return np.float32(0.0)
            if "blocks" in keys:
                return layer_mask.reshape((n_dec,) + (1,) * (np.ndim(leaf) - 1))
            return np.float32(1.0)

        return jax.tree_util.tree_map_with_path(mask_leaf, params)

    # ------------------------------------------------------------------
    # data placement
    # ------------------------------------------------------------------

    def place_batch(self, batch):
        """Host batch -> device arrays sharded batch-dim over (dp, fsdp),
        and — when the mesh has an `sp` axis — seq-dim over sp for every
        rank>=2 leaf whose dim 1 divides evenly (context parallelism).
        Rank-1 leaves (per-row scalars, e.g. GRPO's sequence-level
        advantages) shard their single dim over (dp, fsdp). A batch
        whose rows the data ways do not divide (a store of short final
        chunks: 12 rows on 8 ways) stays replicated, as those chunks
        were stored: padding would train on the repeated rows."""
        sp = self.mesh.shape["sp"]
        base = data_sharding(self.mesh)
        vec = vector_sharding(self.mesh)
        seq = data_sharding(self.mesh, shard_seq=True) if sp > 1 else base
        ways = self.data_ways()

        def put(x):
            # device-resident leaves (the on-device rollout store) reshard
            # device-to-device; only host leaves pay the upload
            if not isinstance(x, jax.Array):
                x = np.asarray(x)
            if x.ndim and x.shape[0] % ways:
                return jax.device_put(x, replicated_sharding(self.mesh))
            if x.ndim < 2:
                return jax.device_put(x, vec)
            s = seq if (sp > 1 and x.ndim >= 2 and x.shape[1] % sp == 0) else base
            return jax.device_put(x, s)

        return jax.tree_util.tree_map(put, batch)

    def data_ways(self) -> int:
        return self.mesh.shape["dp"] * self.mesh.shape["fsdp"]

    def local_ways(self) -> int:
        """Row-divisibility requirement for THIS process's block of a
        global batch (multi-host: each DATA GROUP contributes 1/G of the
        rows; pp stages within a group replicate them; mesh layout keeps
        a group's rows on its hosts' devices)."""
        ways, gc = self.data_ways(), mh.data_group_count(self.mesh)
        if ways % gc:
            raise ValueError(
                f"dp*fsdp={ways} must be divisible by the data-group "
                f"count {gc} (each host must own whole data shards)"
            )
        return ways // gc

    @staticmethod
    def pad_rows(arr: np.ndarray, target_rows: int) -> np.ndarray:
        """Pad the leading dim to `target_rows` by repeating the last row."""
        n = target_rows - len(arr)
        if n <= 0:
            return arr
        return np.concatenate([arr, np.repeat(arr[-1:], n, axis=0)])

    # ------------------------------------------------------------------
    # generation
    # ------------------------------------------------------------------

    def _lm(self) -> TransformerLM:
        return self.model.lm

    def _get_generate_fn(
        self,
        settings: SamplerSettings,
        shape: Tuple[int, int],
        proc_kwargs: Tuple = (),
    ):
        key = (settings, shape, proc_kwargs)
        if key not in self._generate_fns:
            lm = self._lm()
            make_processor = self.generation_logits_processor
            seq2seq = self.config.model.model_arch_type == "seq2seq"

            model = self.model

            def fn(params, input_ids, attention_mask, rng):
                from trlx_tpu.models.wrappers import _effective_base

                # the processor is built from the LIVE param tree at trace
                # time (ILQL shapes logits with its current Q/V heads);
                # _effective_base merges any LoRA overlay so sampling uses
                # the ADAPTED policy, not the frozen base
                base = _effective_base(model, params)
                if seq2seq:
                    from trlx_tpu.models.seq2seq import generate_seq2seq

                    return generate_seq2seq(
                        lm, base, input_ids, attention_mask, rng,
                        settings,
                        logits_processor=make_processor(
                            params, **dict(proc_kwargs)
                        ),
                    )
                return generate(
                    lm, base, input_ids, attention_mask, rng, settings,
                    logits_processor=make_processor(params, **dict(proc_kwargs)),
                    soft_prompt=(
                        params["prompt"]["embedding"] if "prompt" in params else None
                    ),
                    kv_prefix=params.get("prefix"),
                )

            fn.__name__ = "generate"  # the XLA module is jit_generate
            self._generate_fns[key] = jax.jit(fn)
            if not seq2seq:
                self._note_decode_attn(key)
        return self._generate_fns[key]

    def _note_decode_attn(self, key) -> None:
        """Gauges of a built sampler, once each, in the flight stream
        and the tracker. `gen/decode_attn_fused`: 1 where its decode
        steps run the fused kernel over the int8 cache
        (ops/decode_attention.py), 0 where they run anything else
        (Attention warns with the reason when that is the XLA branch
        over an int8 cache); a fused sampler's grid is kept for the
        `tokens_wait` span's counts. `gen/decode_weights_stationary`: 1
        where its decode steps multiply with the kernel shards each chip
        holds and move the activations (a mesh whose `fsdp` axis shards
        the kernels), 0 where no axis does or GSPMD lays the step out.
        `gen/state_step_fused`, of a model with delta-rule or state-space
        layers alone: 1 where a decode step passes over their recurrent
        state once, in the kernel of ops/state_step.py, 0 where it runs
        the XLA branch (the mixers warn with the reason)."""
        settings, (rows, prompt), _ = key
        virtual = 0
        if "prompt" in self.params:
            virtual = self.params["prompt"]["embedding"].shape[0]
        elif "prefix" in self.params:
            virtual = self.params["prefix"]["k"].shape[1]
        cells = fused_decode_cells(
            self._lm(), rows, virtual, prompt, settings.max_new_tokens
        )
        self._decode_cells[key] = cells
        stationary = settings.max_new_tokens > 1 and decode_weights_stationary(
            self._lm().cfg, self._lm().mesh, rows
        )
        gauge = {
            "gen/decode_attn_fused": int(cells is not None),
            "gen/decode_weights_stationary": int(stationary),
        }
        if {"delta", "ssm"} & set(self._lm().cfg.mixers):
            gauge["gen/state_step_fused"] = int(
                settings.max_new_tokens > 1 and state_step_unfused(self._lm().cfg, self._lm().mesh) is None
            )
        self.obs.gauge(**gauge)
        self._tracker_log(gauge, step=self.iter_count)

    def generation_logits_processor(self, params):
        """Optional logits hook for sampling, given the full param tree.

        Swept gen_kwargs that aren't `SamplerSettings` fields (e.g.
        ILQL's `beta`) arrive here as keyword arguments, so subclasses
        declare the ones they consume; `generate()` rejects names no
        processor parameter matches (the reference delegates the same
        validation to HF `generate`'s kwarg checking)."""
        return None

    def generate(self, input_ids, attention_mask=None, settings=None, **kwargs):
        """Sample continuations for experience collection (parity:
        reference generate/generate_eval :256-288)."""
        settings = settings or self.generate_experience_settings
        # kwargs the sampler doesn't implement belong to the logits
        # processor (the reference hands them to the model's custom
        # generate the same way, e.g. ILQL beta — ref modeling_ilql.py
        # generate(beta=...)); they key the compiled-fn cache because the
        # processor bakes them into the traced computation. Names neither
        # side declares are an error, not a silent drop (HF generate
        # validates its kwargs the same way).
        import inspect

        sampler_fields = {f.name for f in dataclasses.fields(SamplerSettings)}
        proc_fields = {
            name
            for name, p in inspect.signature(
                self.generation_logits_processor
            ).parameters.items()
            if name != "params" and p.kind is not inspect.Parameter.VAR_KEYWORD
        }
        unknown = set(kwargs) - sampler_fields - proc_fields
        # names HF generate knows but this sampler doesn't implement get
        # the same treatment per-call as at config load (the SAME set
        # SamplerSettings.from_gen_kwargs warns on): warn and drop — a
        # config sweeping e.g. num_beams must not load fine then crash
        # evaluate()
        hf_unimplemented = unknown & HF_GEN_KWARGS_UNIMPLEMENTED
        if hf_unimplemented:
            logger.warning(
                "generate(): ignoring HF gen_kwargs this sampler does "
                f"not implement: {sorted(hf_unimplemented)}"
            )
            unknown -= hf_unimplemented
            kwargs = {k: v for k, v in kwargs.items() if k not in hf_unimplemented}
        if unknown:
            raise TypeError(
                f"generate() got kwargs {sorted(unknown)} that neither "
                f"SamplerSettings nor {type(self).__name__}."
                "generation_logits_processor accepts"
            )
        for k, v in kwargs.items():
            # processor kwargs key the compiled-fn cache and are baked
            # into the trace: they must be hashable scalars, one value
            # per call (a swept list like beta=[0,1,100] is the config's
            # sweep axis — callers pass each value separately)
            if k in proc_fields and not (v is None or np.isscalar(v)):
                raise TypeError(
                    f"generate() kwarg {k}={v!r} must be a scalar "
                    "(int/float/bool/str); swept values are passed one "
                    "per call, not as a list"
                )
        proc_kwargs = tuple(
            sorted((k, v) for k, v in kwargs.items() if k in proc_fields)
        )
        kwargs = {k: v for k, v in kwargs.items() if k in sampler_fields}
        if kwargs:
            settings = SamplerSettings.from_gen_kwargs(
                {**settings.__dict__, **kwargs}
            )
        input_ids = np.asarray(input_ids, np.int32)
        if attention_mask is None:
            attention_mask = np.ones_like(input_ids)
        attention_mask = np.asarray(attention_mask, np.int32)

        if self._engine_cfg.enabled and not proc_kwargs:
            if self._engine_eligible():
                return self._engine_generate(input_ids, attention_mask, settings)
            if not self._warned_engine_fallback:
                self._warned_engine_fallback = True
                logger.warning(
                    "ppo.gen_engine.enabled but this run is outside the "
                    "engine's v1 envelope (causal LM, single data group, "
                    "no soft-prompt/prefix adapters): falling back to the "
                    "static sampler"
                )

        # pad the batch rows for sharding divisibility AND up to the widest
        # row count this sampler has already compiled for — a ragged final
        # eval batch then reuses the cached executable instead of
        # recompiling the whole decode loop
        B, P = input_ids.shape
        pc = mh.data_group_count(self.mesh)
        target = B + (-B) % self.local_ways()
        # cache keys hold GLOBAL row counts; compare in local terms
        compiled = [
            shape[0] // pc
            for (s, shape, pk) in self._generate_fns
            if s == settings and pk == proc_kwargs
            and shape[1] == P and shape[0] // pc >= target
        ]
        if compiled:
            target = min(compiled)
        if target != B:
            input_ids = self.pad_rows(input_ids, target)
            attention_mask = self.pad_rows(attention_mask, target)
        with self.mesh:
            # generate fns trace over GLOBAL row counts: shape keys are
            # the global batch shape
            gshape = (input_ids.shape[0] * pc, input_ids.shape[1])
            fn = self._get_generate_fn(settings, gshape, proc_kwargs)
            self.rng, key = jax.random.split(self.rng)
            sharding = data_sharding(self.mesh)
            device_mask = mh.global_from_local(attention_mask, sharding)
            out = fn(
                self.params,
                mh.global_from_local(input_ids, sharding),
                device_mask,
                key,
            )
            # ride the prompt mask along as a DEVICE array: the PPO
            # experience forward consumes it (+ sequences/response_mask)
            # straight from here, skipping a host round-trip per chunk
            out = dict(out, prompt_mask=device_mask)
        # a routed model's counters are scalars of the whole call, not rows
        moe_stats = out.pop("moe_stats", None)
        if target != B:
            if mh.is_multihost():
                # each data group's pad rows sit at the END of its own
                # block INSIDE the global batch (every group padded the
                # same B -> target, shard_list keeps groups equal-sized),
                # so a flat [:B] can't drop them — consumers trim their
                # own group's rows via `real_rows` after mh.local_rows
                # (parity: the reference pads across processes and trims
                # after gather, accelerate_ppo_trainer.py:292-300)
                out = dict(out, real_rows=B)
            else:
                out = jax.tree_util.tree_map(lambda x: x[:B], out)
        if moe_stats:
            out["moe_stats"] = moe_stats
        cells = self._decode_cells.get((settings, gshape, proc_kwargs))
        if cells:  # host numbers, like the counters: not rows
            out["decode_cells"] = cells
        state = state_bytes_per_step(self._lm().cfg, gshape[0])
        if state:  # what a decode step of delta-rule layers carries, for the same span
            out["decode_state_bytes"] = state
        return out

    def generate_eval(self, input_ids, attention_mask=None, **kwargs):
        return self.generate(
            input_ids, attention_mask, settings=self.generate_settings, **kwargs
        )

    # ------------------------------------------------------------------
    # rollout decode engine (ppo.gen_engine.*)
    # ------------------------------------------------------------------

    def _engine_eligible(self) -> bool:
        """v1 envelope of the decode engine: causal LM, one data group
        (the rollout-worker geometry), plain sampling (no per-call
        logits processor, no soft-prompt/prefix adapters). LoRA is fine:
        the engine samples the merged effective base like the static
        sampler does."""
        if self.config.model.model_arch_type == "seq2seq":
            return False
        cfg = self.model.cfg
        if cfg.beyond_dense:
            raise NotImplementedError(
                "ppo.gen_engine: the paged decode engine has no latent page pool, keeps no "
                "recurrent state for delta-rule (KDA) or state-space (Mamba-2) layers and runs "
                "no routed or multi-stream layer; use the static sampler for this model"
            )
        if mh.is_multihost() or mh.data_group_count(self.mesh) != 1:
            return False
        if self.generation_logits_processor(self.params) is not None:
            return False
        if "prompt" in self.params or "prefix" in self.params:
            return False
        return True

    def _engine_spec(self, batch: int):
        """Resolve the decode-engine spec for a call's batch width,
        with the memory doctor's pool degradation applied: each
        shrink_pool rung scales slots (and any explicit pool_pages)
        by ``train.memory.pool_shrink_factor`` — fewer lanes, smaller
        pool, same output contract (the queue just drains in more
        refill waves). Speculative decoding derives the draft's shared
        trunk depth here (hydra reference: its branch is the top-k
        layers, so the composed draft shares the other L-k with the
        policy — stored ONCE in the extended pool), which keeps the
        spec the jit traces and the bytes the memory doctor plans in
        agreement by construction."""
        spec = self._engine_cfg.resolve(batch, self._lm().cfg)
        if spec.spec_decode:
            from trlx_tpu.models.gen_engine import hydra_shared_trunk_layers

            L = self._lm().cfg.n_layer
            ref = getattr(self, "ref_params", None)
            if ref is not None and "blocks" in ref:
                kb = jax.tree_util.tree_leaves(ref["blocks"])[0].shape[0]
            else:
                kb = getattr(self.config.model, "num_layers_unfrozen", -1)
            sh = hydra_shared_trunk_layers(L, kb)
            if sh:
                spec = dataclasses.replace(spec, draft_shared_layers=sh)
        scale = self.memdoctor.pool_scale() if self.memdoctor.enabled else 1.0
        if scale < 1.0:
            spec = dataclasses.replace(
                spec,
                slots=max(1, int(spec.slots * scale)),
                pool_pages=(
                    max(1, int(spec.pool_pages * scale))
                    if spec.pool_pages else 0
                ),
            )
        return spec

    def _decode_impl(self) -> str:
        """Provenance string for the flight recorder: which decode
        implementation produces this run's rollout tokens (so a
        recorded telemetry.json says which kernel its tok/s headline
        came from) — what RUNS, not what was configured: an engine that
        is enabled but outside its envelope is the static sampler, and
        an engine whose lane groups the mesh cannot place (or that has
        one group on a mesh with several data ways) is one replicated
        dispatch: every chip decodes the whole queue."""
        if not (self._engine_cfg.enabled and self._engine_eligible()):
            return "static"
        if not self._engine_cfg.paged:
            impl = "engine-contiguous"
        else:
            impl = f"engine-paged-{self._engine_cfg.paged_attention_impl}"
        groups = self._engine_cfg.data_groups
        if groups > 1:
            impl += f"-x{groups}"
        placed = groups > 1 and self._engine_group_sharding(groups) is not None
        if self.data_ways() > 1 and not placed:
            impl += "-replicated"
        return impl

    def _engine_group_sharding(self, groups: int):
        """NamedSharding that places each engine lane group's state on
        its own slice of the mesh's data axes (None when the geometry
        doesn't divide — the groups then run as one replicated stacked
        dispatch, which is still correct, just not multi-chip)."""
        from jax.sharding import NamedSharding, PartitionSpec

        for axes in (("dp", "fsdp"), ("dp",)):
            size = 1
            for ax in axes:
                size *= self.mesh.shape.get(ax, 1)
            if size > 1 and groups % size == 0:
                return NamedSharding(self.mesh, PartitionSpec(axes))
        return None

    def _get_engine_fn(self, settings: SamplerSettings, shape: Tuple[int, int]):
        from trlx_tpu.models.gen_engine import (
            compose_draft_params,
            engine_generate_grouped,
        )

        spec = self._engine_spec(shape[0])
        key = (settings, shape, spec)
        if key not in self._engine_fns:
            lm = self._lm()
            model = self.model
            gshard = (
                self._engine_group_sharding(spec.data_groups)
                if spec.data_groups > 1 else None
            )

            if spec.spec_decode:

                def fn(params, ref_params, input_ids, attention_mask, rng):
                    from trlx_tpu.models.wrappers import _effective_base

                    base = _effective_base(model, params)
                    draft = compose_draft_params(lm.cfg, base, ref_params)
                    return engine_generate_grouped(
                        lm, base, input_ids, attention_mask, rng, settings,
                        spec, draft_params=draft, group_sharding=gshard,
                    )

            else:

                def fn(params, input_ids, attention_mask, rng):
                    from trlx_tpu.models.wrappers import _effective_base

                    return engine_generate_grouped(
                        lm, _effective_base(model, params), input_ids,
                        attention_mask, rng, settings, spec,
                        group_sharding=gshard,
                    )

            fn.__name__ = "engine_generate"
            self._engine_fns[key] = jax.jit(fn)
        return self._engine_fns[key], spec

    def _engine_generate(self, input_ids, attention_mask, settings):
        """Run one generate() chunk through the decode engine. The whole
        chunk is the engine's device-resident prompt queue: finished
        slots refill from it, so the step batch stays dense while the
        chunk drains. Output contract matches the static sampler, plus
        `gen_stats` (refills / real tokens / occupancy / truncation)."""
        from trlx_tpu.parallel.mesh import replicated_sharding

        B, P = input_ids.shape
        with self.mesh:
            fn, spec = self._get_engine_fn(settings, (B, P))
            self.rng, key = jax.random.split(self.rng)
            # the engine's control flow (slot refills, page allocation)
            # runs replicated; the single-replica rollout geometry is
            # the v1 target (ROADMAP item 1's inference workers)
            sharding = replicated_sharding(self.mesh)
            dev_ids = jax.device_put(input_ids, sharding)
            dev_mask = jax.device_put(attention_mask, sharding)
            if spec.spec_decode:
                ref = getattr(self, "ref_params", None)
                if ref is None:
                    raise ValueError(
                        "ppo.gen_engine.spec_decode needs a frozen "
                        "reference model (PPO) to draft from"
                    )
                out = fn(self.params, ref, dev_ids, dev_mask, key)
            else:
                out = fn(self.params, dev_ids, dev_mask, key)
            out = dict(out, prompt_mask=dev_mask)
        return out

    # ------------------------------------------------------------------
    # live-traffic serving tier (train.serve.*)
    # ------------------------------------------------------------------

    def _serve_spec(self):
        """The serving engine geometry: a FIXED spec (one compiled
        executable for the whole run) over a persistent warm pool,
        resolved like the rollout engine's but against the serve
        config's row budget instead of a chunk width."""
        import dataclasses as _dc

        from trlx_tpu.models.gen_engine import EngineSpec
        from trlx_tpu.ops import paged_kv

        cfg = self._serve_cfg
        lm_cfg = self._lm().cfg
        quant = cfg.kv_quant
        if quant is None:
            quant = "int8" if lm_cfg.kv_cache_quant == "int8" else "none"
        slots = min(cfg.slots or cfg.max_batch, cfg.max_batch)
        MP = paged_kv.pages_per_slot(
            cfg.max_prompt_len, cfg.max_new_tokens, cfg.page_size
        )
        return EngineSpec(
            slots=slots,
            page_size=cfg.page_size,
            paged=True,
            pool_pages=cfg.pool_pages or (1 + slots * MP),
            refill_width=0,
            spec_decode=False,
            kv_quant=None if quant == "none" else quant,
            # serve decode rides the SAME kernel selection as rollout
            # decode: one knob (method.gen_engine.paged_attention_impl)
            # decides which attend implementation every engine call —
            # training or serving — runs on (docs/serving.md)
            paged_attention_impl=self._engine_cfg.paged_attention_impl,
        )

    def _serve_start(self) -> None:
        """Build the serving frontend at learn() start (train.serve.*).
        Serving shares the engine machinery and the LIVE policy params
        but owns its rng, pool and executables — the training stream is
        untouched by construction."""
        if not self._serve_cfg.enabled or self.serve is not None:
            return
        if not self._engine_eligible():
            raise ValueError(
                "train.serve.enabled requires the decode engine's v1 "
                "envelope: causal LM, single data group, no "
                "soft-prompt/prefix adapters"
            )
        from trlx_tpu.models.gen_engine import engine_generate
        from trlx_tpu.models.generation import SamplerSettings
        from trlx_tpu.parallel.mesh import replicated_sharding
        from trlx_tpu.serve.frontend import ServeFrontend

        spec = self._serve_spec()
        settings = SamplerSettings.from_gen_kwargs(
            {
                **self.generate_settings.__dict__,
                "max_new_tokens": self._serve_cfg.max_new_tokens,
            }
        )
        lm = self._lm()
        model = self.model

        groups = self._serve_cfg.groups
        if groups > 1:
            # sharded serve lanes: G independent warm pools/ledgers
            # (trlx_tpu/serve/frontend.py owns the grouping), served by
            # ONE stacked vmap dispatch whose group axis shards over
            # the mesh's data axes when the geometry divides — the
            # serve frontend itself becomes multi-chip. Request streams
            # are per-request-id RNG, so tokens are invariant to the
            # group count by construction.
            def fn(params, q_ids, q_mask, rng, row_budget, warm, q_pin,
                   q_ready, q_rng_row):
                from trlx_tpu.models.wrappers import _effective_base

                base = _effective_base(model, params)

                def one_group(ids, mask, budget, w, pin, ready, rngrow):
                    return engine_generate(
                        lm, base, ids, mask, rng, settings, spec,
                        row_budget=budget, warm=w, q_pin=pin,
                        q_ready=ready, q_rng_row=rngrow,
                    )

                return jax.vmap(one_group)(
                    q_ids, q_mask, row_budget, warm, q_pin, q_ready,
                    q_rng_row,
                )

        else:

            def fn(params, q_ids, q_mask, rng, row_budget, warm, q_pin,
                   q_ready, q_rng_row):
                from trlx_tpu.models.wrappers import _effective_base

                return engine_generate(
                    lm, _effective_base(model, params), q_ids, q_mask, rng,
                    settings, spec, row_budget=row_budget, warm=warm,
                    q_pin=q_pin, q_ready=q_ready, q_rng_row=q_rng_row,
                )

        fn.__name__ = "serve_engine_generate"
        jfn = jax.jit(fn)
        gshard = (
            self._engine_group_sharding(groups) if groups > 1 else None
        )

        def runner(q_ids, q_mask, rng, row_budget, warm, q_pin, q_ready,
                   q_rng_row):
            with self.mesh:
                sharding = gshard or replicated_sharding(self.mesh)
                return jfn(
                    self.params,
                    jax.device_put(q_ids, sharding),
                    jax.device_put(q_mask, sharding),
                    rng, row_budget, warm, q_pin, q_ready, q_rng_row,
                )

        lm_cfg = lm.cfg
        geom = {
            "P": self._serve_cfg.max_prompt_len,
            "N": self._serve_cfg.max_new_tokens,
            "page_size": spec.page_size,
            "pool_pages": spec.pool_pages,
            "pad_token_id": settings.pad_token_id,
            "n_layer": lm_cfg.n_layer,
            "n_kv_head": lm_cfg.n_kv_head,
            "head_dim": lm_cfg.head_dim,
            "kv_quant": spec.kv_quant,
            "dtype": lm_cfg.dtype,
            "groups": groups,
        }
        self.serve = ServeFrontend(
            self._serve_cfg, runner, geom,
            self.config.train.checkpoint_dir,
            chaos=self.chaos, obs=self.obs,
        )
        self._serve_final_summary = None

    def _serve_tick(self, iter_count: int) -> None:
        """One lane-refill decision point: pending serve requests run
        BEFORE the next training dispatch (serving outranks training
        refills; the allowance is bounded by
        serve.max_batches_per_tick, so training backfills right after
        — reported when starved, never wedged). A serving failure must
        never take the training loop down: it logs loudly and the next
        tick retries."""
        if self.serve is None:
            return
        with self.watchdog.phase("serve", step=iter_count):
            try:
                self.serve.tick(iter_count)
            except Exception:
                logger.exception(
                    "serve tick failed — serving degrades this tick, "
                    "training continues"
                )

    def _serve_close(self) -> None:
        if self.serve is None:
            return
        try:
            # close() FIRST: the final summary must include the
            # shutdown cancellations and result flush it performs
            self.serve.close()
            summary = self.serve.stats_summary()
            self._serve_final_summary = summary
            self.obs.record("serve_summary", **{
                k: v for k, v in summary.items()
                if isinstance(v, (int, float))
            })
        finally:
            self.serve = None

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def decode(
        self,
        prompts,
        samples,
        prompt_sizes=None,
        append_eos_token: bool = False,
    ) -> Tuple[List[str], List[str], List[str]]:
        """Token arrays -> (str_samples, str_prompts, str_outputs), with
        stop-sequence trimming and EOS recovery (parity: reference
        accelerate_base_trainer.py:203-255)."""
        if prompt_sizes is None:
            prompt_sizes = [np.shape(p)[-1] for p in prompts]

        str_samples, str_prompts, str_outputs = [], [], []
        eos_id = getattr(self.tokenizer, "eos_token_id", None)
        pad_id = getattr(self.tokenizer, "pad_token_id", None)
        eos_token = getattr(self.tokenizer, "eos_token", "") or ""
        for prompt, sample, prompt_size in zip(prompts, samples, prompt_sizes):
            prompt, sample = np.asarray(prompt), np.asarray(sample)
            output_start = 0 if self.config.model.model_arch_type == "seq2seq" else int(prompt_size)
            str_prompt = self.tokenizer.decode(
                prompt[: int(prompt_size)], skip_special_tokens=True
            )
            str_output = self.tokenizer.decode(
                sample[output_start:], skip_special_tokens=True
            )
            trimmed = False
            for stop in self.stop_sequences:
                stop_ix = str_output.find(stop)
                if stop_ix >= 0:
                    str_output = str_output[:stop_ix].rstrip()
                    trimmed = True
            if append_eos_token and (
                trimmed or sample[-1] == eos_id or sample[-1] == pad_id
            ):
                str_output += eos_token
            str_prompts.append(str_prompt)
            str_outputs.append(str_output)
            if self.config.model.model_arch_type == "seq2seq":
                sep = getattr(self.tokenizer, "sep_token", "") or ""
                str_samples.append(str_prompt + sep + str_output)
            else:
                str_samples.append(str_prompt + str_output)
        return str_samples, str_prompts, str_outputs

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------

    def evaluate(self) -> Dict[str, Any]:
        """Sample eval prompts; score with reward_fn/metric_fn (parity:
        reference evaluate :339-505, incl. gen-kwarg sweeping)."""
        with self.watchdog.phase("eval", step=self.iter_count):
            return self._evaluate()

    def _evaluate(self) -> Dict[str, Any]:
        logger.info("Evaluating model")
        import time as _time

        if self.generate_sweep_kwarg is not None:
            sweep_arg, sweep_values = self.generate_sweep_kwarg
        else:
            sweep_arg, sweep_values = None, [None]

        stats: Dict[str, Any] = {}
        table_rows = []
        for sweep_value in sweep_values:
            suffix = f"@{sweep_arg}={sweep_value}" if sweep_value is not None else ""
            all_samples, all_prompts, all_sizes = [], [], []
            all_metadata: Dict[str, list] = {}
            generate_time = _time.time()
            for batch in self.eval_dataloader:
                # per-batch heartbeat: a long healthy eval keeps beating,
                # a single wedged generate goes silent past the deadline
                self.watchdog.beat("eval", step=self.iter_count)
                kwargs = {sweep_arg: sweep_value} if sweep_value is not None else {}
                out = self.generate_eval(batch.input_ids, batch.attention_mask, **kwargs)
                # multi-host: decode/score only this host's rows; scalar
                # stats are all-gathered below. A ragged final batch
                # comes back padded with `real_rows` marking this
                # group's real count — trim after the local extraction.
                sequences = mh.local_rows(out["sequences"])
                sequences = sequences[: out.get("real_rows", len(sequences))]
                all_samples.extend(sequences)
                all_prompts.extend(np.asarray(batch.input_ids))
                all_sizes.extend([np.shape(batch.input_ids)[1]] * len(sequences))
                for k, v in (batch.metadata or {}).items():
                    all_metadata.setdefault(k, []).extend(v)
            stats["time/generate"] = _time.time() - generate_time

            str_samples, str_prompts, str_outputs = self.decode(
                all_prompts, all_samples, all_sizes
            )
            columns = ["prompt", "output"]
            columns_data = [str_prompts, str_outputs]

            if self.reward_fn:
                rewards = self._call_reward_fn(
                    samples=str_samples,
                    prompts=str_prompts,
                    outputs=str_outputs,
                    tokenizer=self.tokenizer,
                    **all_metadata,
                )
                rewards = [
                    float(np.sum(r)) if np.ndim(r) else float(r) for r in rewards
                ]
                columns.append("reward")
                columns_data.append(rewards)
                stats[f"reward/mean{suffix}"] = float(
                    np.mean(mh.allgather(np.asarray(rewards, np.float32)))
                )
            if self.metric_fn:
                metric_time = _time.time()
                metrics = self.metric_fn(
                    samples=str_samples, prompts=str_prompts, outputs=str_outputs,
                    **all_metadata,
                )
                stats["time/metric"] = _time.time() - metric_time
                stats.update(
                    {
                        f"metrics/{k}{suffix}": float(
                            np.mean(mh.allgather(np.asarray(xs, np.float32)))
                        )
                        for k, xs in metrics.items()
                    }
                )
                for metric, values in metrics.items():
                    if isinstance(values, float):
                        continue
                    columns.append(metric)
                    columns_data.append(list(values))
            if sweep_arg is not None:
                columns.insert(0, sweep_arg)
                columns_data.insert(0, [sweep_value] * len(str_prompts))
            table_rows.extend(list(zip(*columns_data)))

        title = f"Evaluation #{self.nth_evaluation}"
        for k, x in stats.items():
            if k.startswith("reward") or k.startswith("metrics"):
                title += f" {k}: {significant(x)}"
        shown = table_rows[: max(8, len(sweep_values))]
        logger.info(
            "\n%s",
            logging.format_table(
                title, columns, [[significant(x) for x in row] for row in shown]
            ),
        )

        self.nth_evaluation += 1
        return stats

    # ------------------------------------------------------------------
    # the training loop
    # ------------------------------------------------------------------

    def _grads_view(self, params):
        """`params` as the loss reads them: differentiated through a
        grads_dtype view, gradients come out in that dtype (e.g. bf16 =
        half the HBM of fp32 grads); `params` stays the fp32 master the
        optimizer updates (the 1.3B recipe,
        configs/mesh/single_chip_1p3b.yml)."""
        gd = self.config.train.grads_dtype
        if not gd:
            return params
        return jax.tree_util.tree_map(
            lambda x: x.astype(_DTYPES[gd])
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params,
        )

    def _step_update(self, params, opt_state, batch, trunk=None):
        """Pure (jit-traceable) single optimizer step: microbatch scan ->
        mean grads -> masked optimizer update. `trunk`: what the fused
        block holds of the frozen trunk for these rows (`_block_trunk`:
        captures by row, the pass's counters), handed on to the loss; the
        captures split with the microbatches."""
        num_mb, mb_size = self.num_mb, self.mb_size
        tx = self.tx
        gd = self.config.train.grads_dtype
        grads_dtype = _DTYPES[gd] if gd else None
        view = self._view

        def compute(p, b, captures=None):
            held = () if trunk is None else ((captures, trunk[1]),)
            out, grads = jax.value_and_grad(self.loss, has_aux=True)(self._grads_view(p), b, *held)
            # a stacked leaf's gradient comes out zero-padded to `[L, ...]`:
            # the static slice of the pad is the trained rows' gradient itself,
            # and nothing below (accumulation, guard, norm, optimizer) sees more
            return out, trainable_view.cut(grads, view)

        if num_mb == 1:
            (loss, stats), grads = compute(params, batch, trunk and trunk[0])
        else:
            # gradient-accumulation compensation hook: batch-statistic
            # terms (PPO's advantage whitening) are precomputed over
            # the FULL minibatch here, so splitting cannot change them
            batch = self._pre_accum_batch(batch)
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((num_mb, mb_size) + x.shape[1:]),
                (batch, trunk and trunk[0]),
            )
            first = jax.tree_util.tree_map(lambda x: x[0], mbs)
            (l_shape, s_shape), g_shape = jax.eval_shape(
                lambda p, mb: compute(p, *mb), params, first)
            # low-precision per-microbatch grads still ACCUMULATE in fp32
            # (bf16 running sums lose mantissa against a growing total)
            zeros = jax.tree_util.tree_map(
                lambda s: jnp.zeros(
                    s.shape,
                    jnp.float32
                    if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype,
                ),
                (g_shape, l_shape, s_shape),
            )

            def body(acc, mb):
                (l, s), g = compute(params, *mb)
                return jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(a.dtype), acc, (g, l, s)
                ), None

            (g_sum, l_sum, s_sum), _ = jax.lax.scan(body, zeros, mbs)
            grads = jax.tree_util.tree_map(lambda x: x / num_mb, g_sum)
            if grads_dtype is not None:
                grads = jax.tree_util.tree_map(
                    lambda x: x.astype(grads_dtype), grads
                )
            loss = l_sum / num_mb
            stats = jax.tree_util.tree_map(lambda x: x / num_mb, s_sum)

        if self.guardrails.enabled and self.guardrails.cfg.grad_norm_max > 0:
            # the watchdog watches the global grad norm; computed in-graph
            # (one reduction over the grads already in registers) and
            # riding the existing async stats copy — no extra host sync
            stats = dict(stats, **{"losses/grad_norm": optax.global_norm(grads)})
        guard = self.config.train.skip_nan_updates
        good = None
        if guard:
            # a poisoned update is detectable from the loss OR the grads:
            # with grads_dtype="bfloat16" a backward-pass overflow can
            # produce inf grads under a perfectly finite loss, and those
            # must not reach params (a checkpoint of poisoned params
            # would brick the relaunch loop)
            good = jnp.isfinite(loss) & jax.tree_util.tree_reduce(
                lambda a, g: a & jnp.all(jnp.isfinite(g)),
                grads,
                jnp.asarray(True),
            )
        with jax.named_scope("optimizer_update"):
            # the optimizer walks the view (`ops/trainable_view.py`): the
            # rows the mask trains, of parameters and state alike, written
            # back into the donated leaves in place
            marks = trainable_view.state_view(tx, opt_state, view)
            old_params = trainable_view.cut(params, view)
            old_state = trainable_view.cut(opt_state, marks)
            if hasattr(tx, "fused_apply"):
                # what is left of the freeze mask on the view (leaves that
                # fell back to a whole walk) streams through the fused apply
                # itself (O(chunk) extra memory); blending frozen values back
                # after the apply would hold THREE fp32 param trees at peak —
                # measured as the 0.5 GB that OOMed the 1.3B recipe. The
                # NaN guard must respect the same budget, so here it zeroes
                # the gradients BEFORE the apply instead of selecting whole
                # trees after it: a poisoned step degrades to a weight-decay
                # -only update (no NaN ever reaches params/moments), and the
                # host-side abort counter still trips on persistent NaN.
                if guard:
                    # where, not multiply: NaN grads * 0 is still NaN
                    grads = jax.tree_util.tree_map(
                        lambda g: jnp.where(good, g, jnp.zeros_like(g)), grads
                    )
                new_params, new_opt_state = tx.fused_apply(
                    old_params, grads, old_state,
                    mask=trainable_view.view_mask(self._update_mask, view),
                )
            else:
                updates, new_opt_state = tx.update(grads, old_state, old_params)
                new_params = optax.apply_updates(old_params, updates)
                if guard:
                    # NaN/inf guard must live INSIDE the trace: params and
                    # opt_state are donated, so by the time the host could
                    # inspect the loss the pre-update buffers are gone. The
                    # traced select commits the old state when the update is
                    # poisoned; the abort counter lives in the learn loop.
                    new_params = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(good, n, o), new_params, old_params
                    )
                    new_opt_state = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(good, n, o), new_opt_state, old_state
                    )
            new_params = trainable_view.paste(params, new_params, view)
            new_opt_state = trainable_view.paste(opt_state, new_opt_state, marks)
        if guard:
            # fold the skip signal into the returned loss: the host's
            # isfinite check then catches finite-loss/bad-grad skips too,
            # with zero extra device->host transfers
            loss = jnp.where(good, loss, jnp.float32(jnp.nan))
        return new_params, new_opt_state, loss, stats

    def _pre_accum_batch(self, batch):
        """Subclass hook, traced inside the jitted step when the
        minibatch is split into accumulation microbatches: precompute
        any batch-statistic-coupled terms over the FULL minibatch so
        the split step stays numerically equal to the unsplit one
        (PPO precomputes whitened GAE advantages when the memory
        doctor's split_microbatch rung is active). Default: identity."""
        return batch

    def _note_backward_depth(self, hoisted: int = 0) -> None:
        """Gauges, once per built train step, in the flight stream and
        the tracker: `model/layers`; `model/backward_layers`, the layers
        the step's backward pass runs through (the wrapper's
        `frozen_below()`: hydra PPO stops it at the branch point);
        `model/trunk_layers_hoisted`, the layers whose forward the built
        program runs once a block and not once a step (`_block_trunk`; 0
        wherever the step keeps the whole forward); and
        `optim/params_walked` beside `optim/params_trained`, the elements
        the step streams through the optimizer and those the freeze mask
        trains: the difference is the frozen part of the leaves the
        trainable view could not cut; `model/head_gathered_once`, 1 where
        the step's chunked log-probabilities gather the head once per call
        and reduce its gradient once (`ops.common.head_gathered_once`: an
        untied head on an `fsdp` mesh), 0 elsewhere."""
        cfg = self.model.cfg
        below = getattr(self.model, "frozen_below", lambda: 0)()
        if self.mesh.shape["pp"] > 1:
            below = 0  # the pipelined forward keeps the full backward
        decoder = getattr(cfg, "n_decoder_layer", None)
        if decoder is None:
            layers, backward = cfg.n_layer, cfg.n_layer - below
        else:  # seq2seq: a frozen trunk takes the whole encoder with it
            layers = cfg.n_layer + decoder
            backward = decoder - below if below else layers
        walked, trained = trainable_view.counts(self.params, self._update_mask, self._view)
        gauges = {"model/layers": layers, "model/backward_layers": backward,
                  "model/trunk_layers_hoisted": hoisted,
                  "optim/params_walked": walked, "optim/params_trained": trained,
                  "model/head_gathered_once": int(self._head_gathered_once())}
        if getattr(cfg, "beyond_dense", False):
            gauges["model/experts_held"] = cfg.n_experts_held or 0
            # of ONE layer that caches; a row's cache is that times the
            # layers that cache (`model/latent_layers`, `model/cache_layers`
            # where some do not)
            gauges["model/cache_elems_per_position"] = cfg.cache_elems_per_position
            gauges["model/residual_streams"] = cfg.residual_streams
            if "delta" in cfg.mixers:
                gauges["model/delta_layers"] = cfg.mixers.count("delta")
                gauges["model/latent_layers"] = cfg.cache_layers
            kinds = {f"model/{kind}_layers": cfg.mixers.count(kind)
                     for kind in ("ssm", "conv") if kind in cfg.mixers}
            if kinds:  # the kinds of layer by count
                gauges.update(kinds)
                gauges["model/cache_layers"] = cfg.cache_layers
                gauges["model/routed_layers"] = cfg.ffns.count("routed")
            if cfg.hybrid:
                gauges["model/state_elems_per_row"] = cfg.state_elems_per_row
        self.obs.gauge(**gauges)
        self._tracker_log(gauges, step=self.iter_count)

    def _head_gathered_once(self) -> bool:
        """The loss's own question (`head_gathered_once` of the projection
        it builds, over a microbatch's rows), asked of the parameters'
        shapes: building the projection may merge an adapter."""
        project_fn = getattr(self.model, "logit_project_fn", None)
        if not self.config.train.logit_chunks or project_fn is None:
            return False
        answer = []
        jax.eval_shape(
            lambda p: answer.append(head_gathered_once(project_fn(p), self.mb_size)), self.params
        )
        return answer[0]

    def _pinned_state_shardings(self):
        # Pin output shardings to the current (input) shardings: without
        # this, GSPMD may choose different layouts for the step-1 outputs,
        # and the changed input shardings force a full retrace+recompile of
        # the train step on step 2.
        params_sh = jax.tree_util.tree_map(lambda x: x.sharding, self.params)
        opt_sh = jax.tree_util.tree_map(lambda x: x.sharding, self.opt_state)
        return params_sh, opt_sh

    def make_train_step(self):
        """One jitted function per optimizer step. Donates params/opt_state."""
        self._note_backward_depth()
        params_sh, opt_sh = self._pinned_state_shardings()

        def train_step(params, opt_state, batch):
            return self._step_update(params, opt_state, batch)

        return jax.jit(
            train_step,
            donate_argnums=(0, 1),
            out_shardings=(params_sh, opt_sh, None, None),
        )

    def trunk_layers_held(self) -> int:
        """Subclass hook, static: the layers of a frozen trunk whose
        output the fused block computes once (`trunk_constants`) and holds
        across its optimizer steps; 0 = every step runs the whole
        forward."""
        return 0

    def trunk_constants(self, params, batch):
        """Subclass hook, traced where `trunk_layers_held()` is above 0:
        (captures, counters) of the frozen trunk over `batch`'s rows, what
        the loss takes as `trunk`: arrays with the rows leading, and the
        pass's counters (sums over the rows, or None)."""
        raise NotImplementedError

    def _block_trunk(self, params, full_batch, n_steps: int):
        """The frozen trunk's forward over all rows of the block, ONCE,
        with the parameters the block was entered with (the freeze mask
        leaves them bit-identical from step to step): in groups of the
        step's batch size, so it runs at the shapes the step would run it
        at. Returns (captures [rows, ...], counters) or None. The counters
        of the pass are spread over the block's steps (and a step's
        microbatches), so that the mean of the steps' stats still reads
        what the program ran, a step's mean."""
        if not self.trunk_layers_held():
            return None
        bs = self.config.train.batch_size
        rows = jax.tree_util.tree_leaves(full_batch)[0].shape[0]
        view = self._grads_view(params)
        if rows == bs:
            captures, counters = self.trunk_constants(view, full_batch)
        else:
            groups = -(-rows // bs)

            def grouped(x):
                if groups * bs != rows:  # a ragged last group wraps round
                    x = x[jnp.arange(groups * bs) % rows]
                return x.reshape((groups, bs) + x.shape[1:])

            captures, counters = jax.lax.map(
                lambda group: self.trunk_constants(view, group),
                jax.tree_util.tree_map(grouped, full_batch),
            )
            captures = jax.tree_util.tree_map(
                lambda x: x.reshape((groups * bs,) + x.shape[2:]), captures)
            counters = jax.tree_util.tree_map(lambda x: jnp.sum(x, axis=0), counters)
        share = n_steps * self.num_mb
        return captures, jax.tree_util.tree_map(lambda x: x / share, counters)

    def make_fused_train_steps(self):
        """The whole inner loop as ONE jitted call: scan the optimizer
        step over host-chosen minibatch permutations of a device-resident
        epoch batch.

        Dispatch cost is per-call, not per-step: the XLA launch
        overhead and the per-step host sync disappear. The reference
        pays this per minibatch by construction (torch eager loop).

        A frozen trunk's forward is a loop invariant of that scan (the
        same rows, the same weights, every epoch), and XLA cannot hoist it
        by itself: the rows are gathered by `perm` inside the body and
        the parameters are the scan's carry. Where the trainer says so
        (`trunk_layers_held()`: hydra PPO), the trunk runs ONCE before the
        scan over all rows (`_block_trunk`) and every step gathers its
        rows of the output with its `perm` and resumes at the branch
        point. The per-step program (`make_train_step`) has nowhere to
        keep it and runs the whole forward.

        Signature: (params, opt_state, full_batch, perms[n_steps, bs])
        -> (params, opt_state, mean_loss, mean_stats)."""

        def fused_train_step(params, opt_state, full_batch, perms):
            trunk = self._block_trunk(params, full_batch, perms.shape[0])

            def body(carry, perm):
                p, o = carry
                mb = jax.tree_util.tree_map(lambda x: x[perm], full_batch)
                held = trunk and (jax.tree_util.tree_map(lambda x: x[perm], trunk[0]), trunk[1])
                p, o, loss, stats = self._step_update(p, o, mb, held)
                return (p, o), (loss, stats)

            (params, opt_state), (losses, stats) = jax.lax.scan(
                body, (params, opt_state), perms
            )
            mean_stats = jax.tree_util.tree_map(
                lambda x: jnp.mean(x, axis=0), stats
            )
            return params, opt_state, jnp.mean(losses), mean_stats

        self._note_backward_depth(hoisted=self.trunk_layers_held())
        params_sh, opt_sh = self._pinned_state_shardings()
        return jax.jit(
            fused_train_step,
            donate_argnums=(0, 1),
            out_shardings=(params_sh, opt_sh, None, None),
        )

    def _fused_epoch_batch(self):
        """Override to enable `train.fused_inner_loop`: return the full
        inner-epoch training batch as a (pytree, n_rows) pair, or None
        when the trainer cannot provide one (streaming pipelines)."""
        return None

    def _epoch_perms(self, n: int) -> np.ndarray:
        """Stacked minibatch index rows [n_steps, batch_size] covering
        every inner epoch, drawn from the SAME per-epoch seed stream the
        looped path's create_train_dataloader consumes
        (pipeline.epoch_shuffle_order with seed = train.seed + the
        iter_count each epoch's loader would be created at). The scanned
        path therefore trains on minibatches in exactly the order the
        per-step loop would — the golden-equivalence contract
        (tests/test_scanned_epochs.py)."""
        from trlx_tpu.pipeline import epoch_shuffle_order

        bs = self.config.train.batch_size
        n_batches = max(n // bs, 1)
        rows = []
        it = self.iter_count
        for _ in range(self.n_inner_epochs):
            order = epoch_shuffle_order(n, self.config.train.seed + it)
            rows.append(order[: n_batches * bs].reshape(n_batches, -1))
            it += n_batches
        return np.concatenate(rows, axis=0).astype(np.int32)

    def pre_optimization_hook(self, will_continue: bool) -> None:
        """Hook fired right before the fused optimization block is
        dispatched, with every device input for the block already
        enqueued and the param buffers still valid (the block's donation
        invalidates them for any LATER dispatch). PPO uses it to launch
        the next cycle's rollout generation ahead of the block
        (ppo.overlap_rollouts); `will_continue` is False when this block
        reaches total_steps, so nothing is prefetched for a cycle that
        will never run."""

    def _abandon_prefetch(self) -> None:
        """Hook: drop any in-flight cross-cycle prefetch and rewind its
        data cursors (the prefetched work never trains). Called when
        learn() exits."""

    def _finish_train_stats(self, log: bool = True, suppress_abort: bool = False):
        """Materialize + process deferred fused-block metrics: run the
        NaN-abort guard on each block's mean loss, attach the
        host-derived keys (time/step — quantized to the flush boundary
        under async_metrics — and the LR), and log through the tracker.
        With `log=False` the LAST block's stats dict is returned instead
        of logged, for the caller to merge eval results into (any older
        pending blocks are still logged). `suppress_abort` demotes the
        guard's abort to an error log — used on exit paths where raising
        would mask the original control flow. Idempotent."""
        import time as _time

        # the flush is the fused block's device sync point: a wedged
        # collective manifests as this read never returning, so it
        # heartbeats as part of the fused_block phase
        with self.watchdog.phase("fused_block"), self.obs.span("block_wait"):
            entries = self._deferred_train.flush()
        out = None
        for i, (stats, step, meta) in enumerate(entries):
            mean_loss = stats.pop("__mean_loss__")
            n_steps = meta["n_steps"]
            # time/step is only honest at a SYNC flush (log=False: the
            # boundary path materializes right after dispatch, so
            # elapsed is the true block wall). A deferred flush happens
            # after the next rollout phase already ran — reporting that
            # wall as time/step would fabricate a multi-x slowdown, so
            # deferred blocks log only the host dispatch cost per step.
            if not log and i == len(entries) - 1:
                stats["time/step"] = (_time.time() - meta["t0"]) / n_steps
            stats["time/dispatch"] = meta["dispatch_s"] / n_steps
            # LR at the block-START step (what the block actually
            # trained with) — same convention as the per-step loop
            stats["learning_rate_group_0"] = float(
                self.schedule(step - n_steps)
            )
            # watchdog: the block's mean loss (+ grad norm / cycle wall
            # when tracked) is THE health signal the escalation ladder
            # acts on at the next safe point (_run_guardrail_ladder)
            self.guardrails.observe_train(
                step=step, loss=mean_loss,
                grad_norm=stats.get("losses/grad_norm"),
                wall=meta.get("cycle_s"),
            )
            # one fused block counts as ONE bad step for the abort
            # counter: a single poisoned (skipped) step inside the scan
            # taints the block mean even when later steps recovered
            try:
                self._guard_bad_loss(mean_loss)
            except RuntimeError:
                if not suppress_abort:
                    raise
                logger.error(
                    "NaN-abort condition reached while flushing deferred "
                    "stats on an exit path; not re-raising"
                )
            if log or i < len(entries) - 1:
                self._log_fused_block(stats, step, n_steps)
            out = stats
        return out

    def _log_fused_block(self, stats, step: int, n_steps: int) -> None:
        """Console + tracker logging for one fused block (shared by the
        deferred flush and the boundary path, so the two can't drift)."""
        if self.memdoctor.enabled:
            # per-phase HBM peak attribution (memory/peak_<phase>_mb)
            # rides the tracker alongside the block's stats
            stats.update(self.memdoctor.sampler.peak_stats())
        desc = " | ".join(
            f"{k}: {v:.2f}"
            for k, v in stats.items()
            if k.startswith("losses/") or k == "loss"
        )
        logger.info(
            "[step %d/%d] (fused x%d) %s",
            step, self.total_steps, n_steps, desc,
        )
        # pending rollout stats carry an earlier-or-equal step index:
        # flush them first so tracker steps stay monotonic
        self._finish_rollout_stats()
        self._tracker_log(stats, step=step)

    def _learn_fused(self, fused_src, results):
        """All inner epochs in one device call (see make_fused_train_steps).

        Checkpoint/eval interval checks fire when a boundary is crossed
        inside the fused block — same cadence as the unfused loop up to
        quantization to block ends. Steady-state blocks (no boundary
        crossed) keep the host dispatch-only: the block's metrics stay
        on device behind an async copy (DeferredStats) and materialize
        one cycle later, so there is no blocking device read between
        cycle boundaries (train.async_metrics). The NaN guard selects
        per-step inside the scan; host-side the block's MEAN loss is the
        abort signal, evaluated when the stats materialize (at most one
        cycle late)."""
        import time as _time

        # the previous block's metrics land first: their copy streamed
        # under the rollout phase, so this is a free read — and the
        # NaN-abort check runs before any new work is dispatched
        self._finish_train_stats()
        # memory doctor: consume a latched HBM-watermark crossing once
        # per cycle, INDEPENDENT of the guardrails gate (with guardrails
        # on it joins this cycle's trips; off, it logs loudly)
        self._check_memory_watermark()
        if self.guardrails.enabled:
            # pull the just-collected rollout stats early so KL/reward
            # trips are seen BEFORE training on a poisoned batch (the
            # tiny scalar copy was staged at rollout end and has landed
            # by now; flush order matches the logging path, so tracker
            # steps stay monotonic)
            self._finish_rollout_stats()
            if self._run_guardrail_ladder():
                # the cycle was consumed by the action (batch requeued /
                # state rolled back): skip training, let the epoch loop
                # collect fresh experience
                return results, False

        full, n = fused_src
        ways = self.local_ways()
        if n % ways:
            # a short final rollout chunk (prompt set smaller than
            # chunk_size) leaves the store with a row count that does
            # not divide this process's shard count, and device_put
            # rejects uneven batch sharding. Pad rows by tiling modulo
            # n: the perms below only ever index [0, n), so pad rows
            # never train and never touch the running moments — this
            # is placement geometry, not data.
            pad_to = -(-n // ways) * ways
            idx = np.arange(pad_to) % n
            full = jax.tree_util.tree_map(lambda x: x[idx], full)
        bs = self.config.train.batch_size
        n_batches = max(n // bs, 1)
        steps_left = max(self.total_steps - self.iter_count, 1)
        perms = self._epoch_perms(n)[:steps_left]
        n_steps = len(perms)
        # quantization is silent degradation whenever the requested eval
        # cadence doesn't land on fused-block boundaries (finer than one
        # block, or any non-multiple — evals then fire late/irregularly):
        # say so ONCE, or the tracker's eval curve is sparser than the
        # reference's for no visible reason. Judge the NOMINAL block size
        # (a final total_steps-truncated block is not a cadence problem).
        nominal_block = self.n_inner_epochs * n_batches
        if (
            not self._warned_fused_cadence
            and nominal_block > 1
            and self.config.train.eval_interval % nominal_block != 0
        ):
            logger.warning(
                "fused_inner_loop runs %d optimizer steps per device call "
                "and eval_interval=%d is not a multiple: evals quantize to "
                "block boundaries. Lower ppo_epochs or raise batch_size "
                "(fewer steps per block), or disable train.fused_inner_loop "
                "for exact cadence.",
                nominal_block, self.config.train.eval_interval,
            )
            self._warned_fused_cadence = True

        if self._fused_train_step is None:
            self._fused_train_step = self.make_fused_train_steps()
        device_full = self.place_batch(full)
        if self.chaos is not None and self.chaos.consult("nan_loss"):
            # chaos: NaN-poison THIS cycle's epoch batch (a fresh tree —
            # the store's own arrays stay clean, so the burst ends when
            # the schedule says it ends)
            device_full = poison_batch(device_full)
        # cycle-level overlap: the next cycle's rollout generation is
        # dispatched NOW, ahead of the block — device FIFO samples it
        # first, and the host decodes+scores it while the block trains
        self.pre_optimization_hook(self.iter_count + n_steps < self.total_steps)
        t0 = _time.time()
        self.watchdog.beat("fused_block", "start", step=self.iter_count)
        # memory-doctor envelope: a RESOURCE_EXHAUSTED from the block
        # walks the degradation ladder (split microbatch -> remat ->
        # rollback) and RETRIES the same cycle instead of dying — the
        # device inputs are not donated, so a degraded re-dispatch sees
        # the identical batch. Bounded by the rung budgets (the ladder
        # ends in abort, which raises).
        for _attempt in range(self._oom_retry_budget()):
            try:
                if self.chaos is not None and self.memdoctor.enabled:
                    # chaos: simulated OOM at the dispatch point (param
                    # buffers intact, like a compile-time OOM)
                    self.chaos.oom("oom_fused_block")
                if self._fused_train_step is None:
                    # a degradation rung dropped the jitted step
                    self._fused_train_step = self.make_fused_train_steps()
                with self.mesh:
                    self.params, self.opt_state, loss, stats = self._fused_train_step(
                        self.params, self.opt_state, device_full, jnp.asarray(perms)
                    )
                break
            except Exception as e:
                if not (self.memdoctor.enabled and is_oom(e)):
                    raise
                if self._handle_oom(e, "fused_block") == "skip":
                    # rollback consumed the cycle: the epoch loop
                    # collects fresh experience at the restored step
                    self.watchdog.beat("fused_block", "end", step=self.iter_count)
                    return results, False
        else:
            # the retry budget is a backstop against a rung that
            # degrades without relieving the OOM — exhausting it must
            # fail loudly, not fall through with unbound outputs
            raise RuntimeError(
                "memory doctor: fused block still RESOURCE_EXHAUSTED "
                "after exhausting the degradation retry budget"
            )
        dispatch_s = _time.time() - t0
        if self.chaos is not None:
            # chaos: the host wedges right after the block is dispatched
            # — what a stuck device collective looks like from here. The
            # fused_block phase stays silent, so the watchdog deadline
            # is what ends the run (detection -> dump -> snapshot ->
            # stalled abort), not the scheduler's wall clock.
            self.chaos.stall("stall_collective")
        self.watchdog.beat("fused_block", "end", step=self.iter_count + n_steps)
        if self.chaos is not None and self.chaos.consult("sigterm"):
            # chaos: the preemption signal lands while the device is
            # mid-fused-block (dispatch is async) — exactly the worst
            # moment a scheduler reclaim can pick
            import signal as _signal

            os.kill(os.getpid(), _signal.SIGTERM)
        # ONE async device->host copy for loss + every scalar stat,
        # consumed at the next flush point (no blocking fetch here)
        prev = self.iter_count
        self.iter_count += n_steps
        self._policy_version += 1  # one fused block = one staleness unit
        staged = {"__mean_loss__": loss}
        staged.update(
            {k: stats[k] for k in stats if np.ndim(stats[k]) == 0}
        )
        cycle_s = None if self._last_cycle_t0 is None else t0 - self._last_cycle_t0
        self._last_cycle_t0 = t0
        self._deferred_train.stage(
            staged, step=self.iter_count,
            meta={"t0": t0, "n_steps": n_steps, "dispatch_s": dispatch_s,
                  "cycle_s": cycle_s},
        )
        # flight recorder: one optimization cycle = rollout collection
        # + this fused block's host span (the block's DEVICE time
        # materializes at the next flush and lands in the next cycle's
        # fused_block phase — steady-state attribution is consistent)
        self.obs.end_cycle(
            step=self.iter_count, policy_version=self._policy_version,
            n_steps=n_steps,
        )
        for _ in range(self.n_inner_epochs):
            self.post_backward_callback()

        def crossed(interval: int) -> bool:
            return (prev // interval) != (self.iter_count // interval) or (
                self.iter_count >= self.total_steps
            )

        ckpt_every = self.config.train.checkpoint_interval
        ckpt_cross = ckpt_every > 0 and crossed(ckpt_every)
        eval_cross = crossed(self.config.train.eval_interval)
        done = self.iter_count >= self.total_steps
        if (
            ckpt_cross or eval_cross or done
            or not self.config.train.async_metrics
        ):
            # boundary block: materialize this block's stats now (the
            # checkpoint/eval work blocks on the device anyway) and log
            # them merged with any eval results, like the unfused loop
            stats = self._finish_train_stats(log=False)
            if ckpt_cross:
                self._save_checkpoint(self._checkpoint_tag())
            if eval_cross:
                results = self.evaluate()
                stats.update(results)
                self._maybe_save_best(stats)
            self._log_fused_block(stats, self.iter_count, n_steps)
        if not done and self._should_stop(n_steps=n_steps):
            self._preemption_exit()
            done = True
        return results, done

    def _measure_forward(self, device_batch) -> float:
        """Time a jitted loss-only (forward) pass, once per batch shape
        (`train.timing_split`): compile, then measure a second run so the
        number excludes compilation. Probes a single microbatch and scales
        by num_mb so the probe never materializes more activation memory
        than the scanned train step does."""
        import time as _time

        key = _batch_shape_key(device_batch)
        if key in self._measured_forward_times:
            return self._measured_forward_times[key]

        probe_batch = device_batch
        scale = 1.0
        if self.num_mb > 1:
            probe_batch = jax.tree_util.tree_map(
                lambda x: x[: self.mb_size], device_batch
            )
            scale = float(self.num_mb)

        fwd = jax.jit(self.loss)
        with self.mesh:
            to_scalar(fwd(self.params, probe_batch)[0])  # compile + warm
            t0 = _time.time()
            to_scalar(fwd(self.params, probe_batch)[0])
            elapsed = (_time.time() - t0) * scale
        self._measured_forward_times[key] = elapsed
        return elapsed

    @abstractmethod
    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        """Pure jittable loss: (params, device batch) -> (loss, stats)."""

    @abstractmethod
    def prepare_learning(self) -> None:
        """Build train/eval dataloaders, set self.n_inner_epochs/total_steps."""

    @abstractmethod
    def create_train_dataloader(self):
        """Fresh (reshuffled) training dataloader."""

    def post_backward_callback(self) -> None:
        pass

    def post_epoch_callback(self) -> None:
        pass

    def _finish_rollout_stats(self) -> None:
        """Hook: materialize + log any stats the rollout phase deferred
        (PPO starts its device->host stats copy asynchronously so it can
        overlap the train step). Called before train-step tracker logging
        so tracker steps stay monotonic (wandb drops backdated steps)."""

    def add_prompt_pipeline(self, pipeline) -> None:
        raise NotImplementedError

    # -- fault-tolerance helpers ----------------------------------------

    # consecutive exhausted-retry tracker failures before the circuit
    # opens: a PERMANENTLY dead tracker must not charge the full backoff
    # (seconds of sleep) to every subsequent step for the rest of the run
    _TRACKER_CIRCUIT_LIMIT = 3

    def _tracker_log(self, stats: Dict[str, Any], step: int) -> None:
        """tracker.log with retry/backoff; a tracker outage degrades to a
        logged error, never a dead run (metrics are droppable, the
        training state is not). After _TRACKER_CIRCUIT_LIMIT consecutive
        exhausted-retry failures the circuit opens (resilient.
        CircuitBreaker with reset_timeout=0): one un-retried attempt per
        step — so a recovered backend resumes logging — with failures
        swallowed silently."""
        # flight-recorder tap on the ONE stats funnel: telemetry reuses
        # the exact host scalars the run already produces (the two
        # accounting paths cannot drift), and a tracker outage below
        # never costs the flight stream its numbers
        self.obs.observe_stats(stats, step)
        train = self.config.train
        probing = not self._tracker_breaker.is_closed
        if not self._tracker_breaker.allow():  # unreachable at reset=0
            return
        try:
            if probing:
                self.tracker.log(stats, step=step)
            else:
                retry_call(
                    self.tracker.log, stats, step=step,
                    retries=train.external_retries,
                    base_delay=train.retry_base_delay,
                    description="tracker.log",
                )
        except Exception as e:
            self._tracker_breaker.record_failure()
            if not probing:
                logger.error(
                    "tracker.log failed after retries; continuing without "
                    "logging step %d: %s%s", step, e,
                    " (circuit open: further steps attempt once, no backoff)"
                    if not self._tracker_breaker.is_closed else "",
                )
            return
        if probing:
            logger.info("tracker recovered; resuming retried logging")
        self._tracker_breaker.record_success()

    def _reward_fallback_value(self) -> float:
        """Value the fallback reward substitutes per sample when the
        reward service is down and `resilient_io.fallback_reward:
        hold_mean` is configured. PPO overrides with its running-moments
        mean; the base has no reward history, so 0 (neutral after
        running-moment scaling)."""
        return 0.0

    def _chaos_wrapped_reward(self, **kwargs):
        """reward_fn with the chaos fault sites threaded around it (the
        object the ResilientCaller retries — injected timeouts/errors
        exercise the real deadline/backoff/breaker path)."""
        if self.chaos is not None:
            self.chaos.reward_fault_pre()
        out = self.reward_fn(**kwargs)
        if self.chaos is not None:
            out = self.chaos.reward_fault_post(out)
        return out

    def _build_reward_caller(self) -> ResilientCaller:
        """Compose the hardened reward path from train.resilient_io:
        per-attempt deadline, retry/backoff/jitter, circuit breaker and
        fallback. With the default (empty) config this reduces exactly
        to PR 1 semantics: plain retries, final failure propagates."""
        train = self.config.train
        rcfg = self._resilient_cfg
        breaker = None
        fallback = None
        if rcfg.has_fallback:
            if rcfg.breaker_threshold > 0:
                breaker = CircuitBreaker(
                    failure_threshold=rcfg.breaker_threshold,
                    reset_timeout=rcfg.breaker_reset_s,
                )

            def fallback(exc, kwargs):
                n = len(kwargs.get("samples") or [])
                v = (
                    self._reward_fallback_value()
                    if rcfg.fallback_reward == "hold_mean"
                    else float(rcfg.fallback_reward)
                )
                return [v] * n

        return ResilientCaller(
            fn=self._chaos_wrapped_reward,
            description="reward_fn",
            timeout=rcfg.reward_timeout,
            retries=(
                rcfg.retries
                if rcfg.retries is not None else train.external_retries
            ),
            base_delay=(
                rcfg.base_delay
                if rcfg.base_delay is not None else train.retry_base_delay
            ),
            max_delay=rcfg.max_delay,
            jitter=rcfg.jitter,
            breaker=breaker,
            fallback=fallback,
        )

    def _call_reward_fn(self, **kwargs):
        """reward_fn through the resilient caller. Without a configured
        fallback, rewards stay load-bearing: the final failure
        propagates (the preemption path still gets a chance to
        checkpoint via learn()'s finally). With one, a slow or dead
        reward service degrades the run instead of hanging or killing
        it — the overlapped rollout pipeline keeps moving."""
        if self._reward_caller is None:
            self._reward_caller = self._build_reward_caller()
        with self.watchdog.phase("reward", step=self.iter_count):
            if self.chaos is not None:
                # chaos stall_reward: the hang happens BEFORE the
                # resilient caller, so no per-attempt deadline can cut
                # it short — only the watchdog's reward-phase deadline
                # ends it (consulted once per call, not per retry)
                self.chaos.stall("stall_reward")
            return self._reward_caller(**kwargs)

    def _checkpoint_tag(self) -> str:
        return f"checkpoint_{self.iter_count:0{len(str(self.total_steps))}d}"

    # consecutive failed checkpoint commits tolerated before the failure
    # propagates: transient shared-storage flakes must not kill a run
    # whose training state is intact (the next interval retries), but a
    # permanently unwritable store should not fail silently forever
    _CKPT_FAILURE_LIMIT = 3

    def _save_checkpoint(self, name: str, final: bool = False) -> None:
        """Commit a full checkpoint (state + deploy export) atomically
        under checkpoint_dir/<name> via the CheckpointManager.
        ``final=True`` marks an exit-path save (preemption / epoch
        exhaustion): a commit failure there propagates immediately —
        "the next interval retries" does not exist on the way out.

        Health-gated: with guardrails enabled, a commit is SKIPPED while
        the watchdog considers the run unhealthy — with async metrics
        the NaN signal lands one cycle late, and an ungated boundary
        right behind a bad block would publish a poisoned "last good
        checkpoint", the exact state auto-rollback restores. The skip
        decision is process 0's view broadcast to every host (commit()
        is collective)."""
        if self.guardrails.enabled and mh.broadcast_flag(
            not self.guardrails.commit_ok()
        ):
            logger.warning(
                "guardrails: run unhealthy (%s) — skipping checkpoint "
                "commit %r so the last good checkpoint stays good",
                self.guardrails.state_summary(), name,
            )
            return
        logger.info(
            "Saving checkpoint into %s",
            os.path.join(self.config.train.checkpoint_dir, name),
        )

        def write(tmp_dir: str) -> None:
            if self.chaos is not None and self.chaos.consult("ckpt_fail"):
                raise ChaosFault("chaos: injected checkpoint write failure")
            if self.config.train.save_optimizer:
                self.save(tmp_dir)
            self.save_pretrained(os.path.join(tmp_dir, "hf_model"))
            # self-documenting perf artifact: the run's bench-comparable
            # telemetry snapshot commits atomically WITH the checkpoint
            # (same tmp+rename protocol, hashed by the same integrity
            # manifest), so every checkpointed run leaves its own
            # record of what it measured and on which device
            self.obs.write_telemetry(os.path.join(tmp_dir, "telemetry.json"))

        try:
            with self.watchdog.phase("checkpoint", step=self.iter_count):
                final_path = self.ckpt_manager.commit(name, write)
        except mh.BarrierTimeout as e:
            # a peer never reached the save_pretrained barrier: the
            # abandoned worker thread is still parked in that collective,
            # so CONTINUING to train would enqueue device collectives
            # that interleave with it across hosts (the hazard the
            # barrier exists to prevent). This is a detected stall, not
            # a tolerable commit flake — take the stalled exit.
            self._stalled_exit(f"checkpoint commit {name!r}: {e}")
        except Exception as e:
            # the manager's protocol guarantees a failed commit is never
            # discoverable (torn tmp_ dir only) and aborts consistently
            # on every host, so training state is intact — log, count,
            # and continue; the next interval (or the final save) retries
            self._ckpt_commit_failures += 1
            if final or self._ckpt_commit_failures >= self._CKPT_FAILURE_LIMIT:
                raise
            logger.error(
                "checkpoint commit %r failed (%d/%d consecutive before "
                "the failure propagates): %s — training continues; the "
                "next checkpoint interval retries", name,
                self._ckpt_commit_failures, self._CKPT_FAILURE_LIMIT, e,
            )
            return
        self._ckpt_commit_failures = 0
        self.obs.record("checkpoint", name=name)
        if self.watchdog.enabled and self.watchdog.cfg.emergency_snapshot:
            # the commit was health-gated, so the state just persisted
            # is also the freshest "known good" — refresh the host-RAM
            # shadow the hang doctor's emergency snapshot writes from
            self._update_emergency_shadow()
        if self.chaos is not None and self.chaos.consult("ckpt_corrupt"):
            # chaos: silent post-commit storage corruption (a bad DCN
            # write). The consult advances on EVERY host so the
            # schedule stays deterministic; only the primary touches
            # the shared filesystem. Recovery is the integrity
            # manifest's job at the next load.
            if mh.is_main():
                self.chaos.corrupt_checkpoint(final_path)

    def _commit_final_checkpoint(self, reason: str) -> None:
        """Commit the current step's checkpoint before the run exits —
        unless it already committed (e.g. preemption right after an
        interval save; rewriting every shard would re-open the re-commit
        window for nothing). The skip decision is process 0's view
        broadcast to all hosts: commit() is collective, so a host with a
        stale filesystem view deciding differently would deadlock the
        others. ``checkpoint_interval <= 0`` means the run writes no
        step checkpoint at all, this one included."""
        if self.config.train.checkpoint_interval <= 0:
            logger.info("%s: checkpointing is off, nothing committed", reason)
            return
        tag = self._checkpoint_tag()
        # compare parsed STEP numbers, not directory names: the name's
        # zero-pad width tracks run-mutable total_steps (PPO re-derives
        # it from the store), so the same step can print differently
        # across a resume
        ckpts = self.ckpt_manager.step_checkpoints()
        skip = mh.broadcast_flag(
            bool(ckpts) and ckpts[-1][0] == self.iter_count
        )
        if skip:
            logger.info(
                "%s: step %d checkpoint already committed", reason,
                self.iter_count,
            )
            return
        self._save_checkpoint(tag, final=True)
        logger.info(
            "%s: checkpoint committed at step %d", reason, self.iter_count
        )

    def _preemption_exit(self) -> None:
        self._commit_final_checkpoint("preemption; exiting cleanly")

    def _maybe_save_best(self, stats: Dict[str, Any]) -> None:
        """Track the best eval reward and commit best_checkpoint on a new
        high (shared by the fused and unfused loops)."""
        if not self.config.train.save_best:
            return
        reward = stats.get(
            "reward/mean", stats.get("metrics/reward", -float("inf"))
        )
        if reward > self.best_reward:
            self.best_reward = reward
            logger.info("Saving best checkpoint")
            self._save_checkpoint("best_checkpoint")

    # multihost: agree on preemption every N optimizer steps rather than
    # every step — the ANY-reduce is a blocking host collective, and
    # preemption grace periods (30s+) dwarf a few steps of latency
    PREEMPT_SYNC_STEPS = 8

    def _should_stop(self, n_steps: int = 1, force: bool = False) -> bool:
        """Preemption check, agreed across hosts: the signal lands on
        whichever host the scheduler chose, so this is an ANY-reduce
        (mh.any_flag), not a process-0 broadcast. Single-host reads the
        local flag directly; multihost amortizes the collective over
        PREEMPT_SYNC_STEPS steps (every process runs the same control
        flow, so the sync cadence stays in lockstep). `force=True` syncs
        unconditionally — used at coarse boundaries (epoch tops, rollout
        chunks) where the collective is cheap relative to the work."""
        if not mh.is_multihost():
            return self.preemption.requested()
        if not force:
            self._preempt_sync_counter += n_steps
            if self._preempt_sync_counter < self.PREEMPT_SYNC_STEPS:
                return False
            self._preempt_sync_counter = 0
        return mh.any_flag(self.preemption.requested())

    def _guard_bad_loss(self, loss: float) -> bool:
        """Host half of the NaN/inf guard: returns True when the update
        was skipped device-side (non-finite loss). Aborts the run after
        `max_bad_steps` CONSECUTIVE skipped steps — a persistent NaN
        means diverged state, and looping forever on it would burn the
        whole job allocation silently."""
        if not self.config.train.skip_nan_updates or np.isfinite(loss):
            self._bad_steps = 0
            return False
        self._bad_steps += 1
        logger.warning(
            "non-finite loss %s at step %d: update skipped (%d/%d "
            "consecutive bad steps before abort)",
            loss, self.iter_count, self._bad_steps,
            self.config.train.max_bad_steps,
        )
        corrective = self.guardrails.enabled and any(
            a != "log" for a in self.guardrails.cfg.ladder
        )
        if self._bad_steps >= self.config.train.max_bad_steps:
            if corrective:
                # the watchdog owns escalation now: its ladder decides
                # whether this becomes a requeue, an LR cut, a rollback
                # or an abort — raising here would pre-empt a recoverable
                # intervention with a run-fatal one. A log-only ladder
                # cannot intervene, so the legacy abort stays the
                # backstop there (otherwise a persistent NaN would train
                # forever with every checkpoint commit health-gated off).
                logger.warning(
                    "%d consecutive non-finite losses (max_bad_steps=%d); "
                    "deferring the abort to the guardrails escalation "
                    "ladder", self._bad_steps,
                    self.config.train.max_bad_steps,
                )
                return True
            raise RuntimeError(
                f"aborting: {self._bad_steps} consecutive non-finite "
                f"losses (train.max_bad_steps={self.config.train.max_bad_steps}); "
                "the model state has diverged — restart from the last "
                "committed checkpoint with a lower lr / tighter clipping"
            )
        return True

    # -- guardrails (divergence watchdog) -------------------------------

    def _run_guardrail_ladder(self) -> bool:
        """Consume this cycle's watchdog verdict and execute the ladder
        action. Returns True when the cycle must be skipped (the batch
        was requeued / state was rolled back); raises on abort. Called
        once per cycle (fused block / optimizer step) at a point where
        no new device work has been dispatched."""
        # cross-host consistency watchdog first: a detected divergence
        # must join this cycle's trips (and the any_flag agreement
        # below) rather than waiting a cycle
        self._maybe_check_consistency()
        if mh.is_multihost():
            # lockstep: most signals derive from globally-reduced stats
            # and trip identically everywhere, but per-cycle wall time
            # is host-LOCAL by design (a stuck host trips it alone) —
            # and the resulting actions are collective (rollback's
            # allgather/load) or data-divergent (requeue's stream
            # rewind). Agree on "anyone tripped" every cycle so all
            # ladders advance together; one any_flag per cycle is noise
            # next to the rollout phase's collectives.
            peer = mh.any_flag(self.guardrails.has_pending_trips)
            if peer and not self.guardrails.has_pending_trips:
                self.guardrails.peer_trip()
        action = self.guardrails.pending_action()
        if action is not None:
            # the trip rows landed via the guardrail listener as they
            # were recorded; this row is the ladder's DECISION
            self.obs.record(
                "guardrail_action", action=action,
                rung=self.guardrails.state_summary()["rung"],
            )
        if action is None or action == "log":
            return False  # pending_action already logged the trip
        if action == "requeue":
            return self._requeue_poisoned_batch()
        if action == "lr_cut":
            self._apply_lr_cut(self.guardrails.cfg.lr_cut_factor)
            return False
        if action == "rollback":
            return self._rollback_to_last_good()
        # abort: coordinated across hosts via any_flag — every host
        # computes the same verdict from the same global stats, but the
        # agreement makes a pathological divergence (one host seeing
        # different numbers) abort the pod instead of deadlocking it
        if mh.any_flag(action == "abort"):
            raise RuntimeError(
                "guardrails abort: escalation ladder exhausted "
                f"({self.guardrails.state_summary()}); the run did not "
                "recover — relaunch resumes from the last good checkpoint"
            )
        return False

    # -- hang doctor (watchdog escalation + emergency shadow) -----------

    def _on_watchdog_stall(self, report: StallReport) -> None:
        """Monitor-thread escalation (host-side only — the device may
        be wedged, which is exactly why we are here): record the stall
        in the unified guardrails trip history, then persist the
        emergency snapshot from the host-RAM shadow. The watchdog
        aborts with EXIT_STALLED right after this returns."""
        self.guardrails.trip(STALL_SIGNAL, report.summary)
        if self.watchdog.cfg.emergency_snapshot:
            self.ckpt_manager.emergency_snapshot(report={
                "summary": report.summary,
                "phase": report.phase,
                "age_s": round(report.age_s, 3),
                "deadline_s": round(report.deadline_s, 3),
                "step": report.step,
                "timeline": [
                    [round(t, 3), phase, event, step]
                    for t, phase, event, step in report.timeline
                ],
            })

    def _stalled_exit(self, summary: str) -> None:
        """A stall detected OUTSIDE the monitor thread (a timed barrier
        blowing its deadline): route through the watchdog's own
        escalation so the operator gets the identical post-mortem —
        all-thread stacks + phase timeline, the unified `stall` trip
        record, the emergency snapshot — before the stalled exit. Does
        not return under the real abort hook."""
        self.watchdog.trip_external(
            "barrier", summary, step=self.iter_count
        )

    def _update_emergency_shadow(self) -> None:
        """Refresh the CheckpointManager's host-RAM shadow with the
        just-committed (health-gated) state: full host numpy copies of
        params/opt_state plus the resume metadata and topology
        manifest, so a later emergency snapshot persists without
        touching the device. Multihost sharded state is not fully
        host-addressable — skipped with a one-time note there (the
        stall report and stalled exit still fire; each host's last
        committed checkpoint remains the recovery point)."""
        tree = self._state_tree()
        if any(
            isinstance(x, jax.Array) and not x.is_fully_addressable
            for x in jax.tree_util.tree_leaves(tree)
        ):
            if not self._warned_shadow_skip:
                logger.info(
                    "hang doctor: state is sharded across hosts — the "
                    "emergency-snapshot shadow is unavailable (stall "
                    "detection, stack dumps and the stalled exit class "
                    "still apply; recovery point is the last committed "
                    "checkpoint)"
                )
                self._warned_shadow_skip = True
            return
        host_tree = jax.tree_util.tree_map(
            # np.array (not asarray): on CPU a jax.Array view would
            # alias the device buffer, which the next train step DONATES
            lambda x: np.array(x) if isinstance(x, jax.Array) else x,
            tree,
        )
        self.ckpt_manager.update_shadow(
            host_tree,
            self._resume_state_dict(),
            manifests={TOPOLOGY_MANIFEST: self._topology_manifest()},
        )

    # -- memory doctor (preflight / watermarks / OOM ladder) ------------

    def _extra_plan_items(self) -> List:
        """Subclass hook: extra :class:`~trlx_tpu.utils.memdoctor.
        PlanItem` rows folded into the preflight HBM plan (PPO adds the
        teacher-forced experience forward's activation residency)."""
        return []

    def _memory_preflight(self) -> None:
        """Admission control, run at the top of learn() BEFORE any
        model compile: build the analytic per-phase HBM plan and check
        its peak phase against the device budget. ``enforce`` fails an
        over-budget config with the itemized report while the mistake
        still costs seconds; ``warn`` logs the same report."""
        md = self.memdoctor
        if not md.enabled or md.cfg.preflight == "off":
            return
        plan = estimate_plan(self)
        self._hbm_plan = plan
        logger.info("memory doctor preflight:\n%s", plan.report())
        if plan.over_budget():
            msg = (
                "memory doctor: preflight REJECTED this config — the "
                "analytic HBM plan exceeds the admitted budget, and "
                "compiling it would only discover the same thing the "
                "slow way:\n" + plan.report()
            )
            if md.cfg.preflight == "enforce":
                raise MemoryPlanError(msg, plan)
            logger.warning(msg)

    def _check_memory_watermark(self) -> None:
        """Consume a latched watermark trip (and run the ``hbm_creep``
        chaos site) at the once-per-cycle safe point: creeping HBM
        residency raises the ``memory`` guardrail signal and walks the
        PR 3 ladder like any other health trip."""
        if not self.memdoctor.enabled:
            return
        sampler = self.memdoctor.sampler
        if self.chaos is not None and self.chaos.consult("hbm_creep"):
            # chaos: the next readings saturate the watermark — sampled
            # inline so the trip lands THIS cycle deterministically
            sampler.inject_creep()
            for _ in range(self.memdoctor.cfg.watermark_window):
                sampler.sample()
        detail = sampler.consume_trip()
        if detail:
            # recorded directly too: with guardrails off the crossing
            # would otherwise exist only as a log line
            self.obs.record("memory_watermark", detail=detail)
            if self.guardrails.enabled:
                self.guardrails.trip(MEMORY_SIGNAL, detail)
            else:
                # no ladder to walk, but creep headed for an OOM must
                # never pass silently just because guardrails are off
                logger.warning(
                    "memory doctor: %s — logged only (enable "
                    "train.guardrails for the escalation ladder)", detail,
                )

    def _oom_retry_budget(self) -> int:
        """Attempt bound shared by every OOM-retry envelope (fused
        block / per-step / rollout): every rung the ladder could
        possibly walk, plus slack for the terminal rollback/abort —
        the ladder itself terminates (abort raises), this only stops a
        logic bug from spinning."""
        cfg = self.memdoctor.cfg
        return cfg.max_splits + cfg.max_pool_shrinks + 4

    def _oom_caps(self) -> Dict[str, bool]:
        """What the memory doctor's ladder can actually do in THIS run:
        pool shrinking needs the decode engine, a microbatch split
        needs the halved size to stay sharding-divisible, remat can
        only escalate past the configured policy."""
        half = self.mb_size // 2
        can_split = (
            self.mb_size % 2 == 0
            and half >= 1
            and half % self.data_ways() == 0
            and self.config.train.batch_size % (self.num_mb * 2) == 0
        )
        return {
            "shrink_pool": self._engine_cfg.enabled,
            "split_microbatch": can_split,
            "remat": (
                remat_strength(self.memdoctor.cfg.remat_escalation)
                > remat_strength(self.config.train.remat_policy)
            ),
            "rollback": True,  # _rollback_to_last_good degrades gracefully
        }

    def _state_buffers_valid(self) -> bool:
        """After a RUNTIME OOM the failed dispatch may already have
        consumed its donated params/opt-state buffers — retrying with
        deleted arrays would crash; only a restore can recover."""
        try:
            return not any(
                x.is_deleted()
                for x in jax.tree_util.tree_leaves(self._state_tree())
                if isinstance(x, jax.Array)
            )
        except Exception:
            return True

    def _handle_oom(self, exc: BaseException, phase: str) -> str:
        """Classify a RESOURCE_EXHAUSTED and execute one rung of the
        degradation ladder. Returns ``"retry"`` when the failed
        dispatch should be re-attempted under the degraded config,
        ``"skip"`` when the cycle was consumed by a rollback; raises
        the itemized abort when the ladder is exhausted (or the doctor
        is disabled — raw propagation is the pre-doctor behavior)."""
        md = self.memdoctor
        if not md.enabled:
            raise exc
        event = classify_oom(exc, phase)
        # unified trip accounting: the OOM joins the guardrails history
        # (and escalates that ladder too if the run stays unhealthy)
        self.guardrails.trip(MEMORY_SIGNAL, event.summary())
        action = md.decide(event, self._oom_caps())
        # flight recorder: the OOM-ladder rung, in the same correlated
        # stream as the guardrail trip above
        self.obs.record(
            "oom", phase=phase, action=action, detail=event.summary(),
        )
        if action in ("shrink_pool", "split_microbatch", "remat") and (
            not self._state_buffers_valid()
        ):
            # the failed dispatch already consumed its donated buffers:
            # in-place degradation cannot retry — only a restore can
            logger.warning(
                "memory doctor: %s, but the failed step consumed its "
                "donated state buffers — escalating to rollback",
                event.summary(),
            )
            action = "rollback"
        if action == "abort":
            md.note(event, action)
            raise MemoryAbortError(
                md.abort_report(event, self._hbm_plan)
            ) from exc
        md.note(event, action)
        if action == "shrink_pool":
            # drop the engine's compiled fns: the next generate()
            # resolves the spec with the new (smaller) pool scale
            self._engine_fns.clear()
            return "retry"
        if action == "split_microbatch":
            self._apply_accum_factor()
            return "retry"
        if action == "remat":
            self._escalate_remat(md.cfg.remat_escalation)
            return "retry"
        # rollback: restore the last health-gated checkpoint; the
        # degradation state survives it (load() merges by max)
        if self._rollback_to_last_good():
            return "skip"
        raise MemoryAbortError(
            md.abort_report(event, self._hbm_plan)
        ) from exc

    def _apply_accum_factor(self) -> None:
        """Re-derive num_mb/mb_size from the configured microbatch and
        the doctor's accumulation factor, and drop the jitted steps so
        the next dispatch traces the split in. The split is
        golden-checked equal to the unsplit step (same global batch,
        fp32 accumulation — tests/test_memdoctor.py)."""
        base_mb = self.config.train.minibatch_size or self.config.train.batch_size
        mb = max(base_mb // self.memdoctor.accum_factor, 1)
        if self.config.train.batch_size % mb or mb % self.data_ways():
            logger.error(
                "memory doctor: accumulation factor %d does not divide "
                "cleanly (batch %d, base mb %d, dp*fsdp %d) — keeping "
                "the current microbatch", self.memdoctor.accum_factor,
                self.config.train.batch_size, base_mb, self.data_ways(),
            )
            return
        if base_mb < self.config.train.batch_size:
            # the config already accumulated (train.minibatch_size):
            # its loss whitened batch-statistic terms per MICROBATCH
            # (reference parity). The compensation hook precomputes
            # them over the FULL step batch instead — the canonical,
            # num_mb-invariant scope, which further splits preserve
            # exactly — so the first split shifts the whitening
            # statistics relative to the pre-OOM steps. Unavoidable:
            # no compensation can reproduce per-64-row statistics from
            # 32-row microbatches; say so instead of drifting silently.
            logger.warning(
                "memory doctor: config already used microbatch "
                "accumulation (minibatch_size=%d) — the split switches "
                "batch-statistic loss terms (PPO advantage whitening) "
                "from per-microbatch to full-batch scope; numerics are "
                "invariant to any FURTHER splits but differ from the "
                "pre-OOM per-microbatch statistics", base_mb,
            )
        self.mb_size = mb
        self.num_mb = self.config.train.batch_size // mb
        self._train_step = None
        self._fused_train_step = None
        logger.warning(
            "memory doctor: train microbatch split to %d rows "
            "(x%d gradient accumulation; global batch unchanged)",
            mb, self.num_mb,
        )

    def _escalate_remat(self, policy: str) -> None:
        """Switch the run to a stronger activation-checkpoint policy
        (ops/remat.py) and drop every jitted fn that baked the old one
        in. Never weakens a policy the user already configured."""
        self.config.train.remat_policy = policy
        self.memdoctor.note_remat(policy)
        self._drop_traced_fns()
        logger.warning(
            "memory doctor: activation checkpointing escalated to %r — "
            "backward recomputes instead of keeping residuals", policy,
        )

    def _drop_traced_fns(self) -> None:
        """Drop every cached jitted function that traced the remat
        policy in (subclasses extend: PPO adds its experience fns)."""
        self._train_step = None
        self._fused_train_step = None
        self._generate_fns.clear()
        self._engine_fns.clear()


    def _generate_rollout(self, input_ids, attention_mask):
        """generate() under the memory doctor's envelope: a
        RESOURCE_EXHAUSTED from rollout generation (the decode engine's
        prefill is the allocation spike) walks the ladder's
        shrink_pool rung — page pool and slots scale down, the engine
        fns retrace, and the SAME chunk retries. The ``oom_prefill``
        chaos site injects here, once per rollout generate() dispatch.
        Lives on the base trainer so every experience-collecting
        trainer (the online core AND RFT's offline sweep) shares it."""
        for _attempt in range(self._oom_retry_budget()):
            try:
                if self.chaos is not None and self.memdoctor.enabled:
                    self.chaos.oom("oom_prefill")
                # the sampler's dispatch; its device time ends at the
                # `tokens_wait` span (the host pull of the tokens)
                with self.obs.span("generate", rows=len(input_ids)):
                    return self.generate(input_ids, attention_mask)
            except Exception as e:
                if not (self.memdoctor.enabled and is_oom(e)):
                    raise
                # rollout OOMs never return "skip" (rollback is not on
                # the rollout sub-ladder); "retry" loops, abort raises
                self._handle_oom(e, "rollout_prefill")
        raise RuntimeError(
            "memory doctor: rollout generation still RESOURCE_EXHAUSTED "
            "after exhausting the pool-shrink budget"
        )

    def _dispatch_experience(self, fn, *args):
        """Run a jitted teacher-forced scoring forward under the memory
        doctor's classification envelope. An OOM here has no runtime
        relief rung (the forward is inference-shaped: microbatch splits
        and remat don't apply; ``train.logit_chunks`` is the
        config-time fix) — the envelope's value is the classified,
        itemized abort instead of a raw allocator error."""
        try:
            return fn(*args)
        except Exception as e:
            if not (self.memdoctor.enabled and is_oom(e)):
                raise
            self._handle_oom(e, "experience")  # experience -> abort
            raise  # unreachable: the abort above always raises

    def _apply_degradation(self) -> None:
        """Re-apply the doctor's (restored) degradation to the live
        trainer: pool scale, accumulation factor, remat policy. Called
        after load() adopts a persisted ``memory_degrade``."""
        md = self.memdoctor
        if md.pool_shrinks:
            self._engine_fns.clear()
        if md.accum_factor > 1:
            self._apply_accum_factor()
        if md.remat_policy is not None and (
            remat_strength(md.remat_policy)
            > remat_strength(self.config.train.remat_policy)
        ):
            self.config.train.remat_policy = md.remat_policy
            self._drop_traced_fns()

    # -- cross-host consistency watchdog --------------------------------

    def _extra_fingerprint(self) -> Dict[str, float]:
        """Subclass hook: extra host-side scalars folded into the
        consistency fingerprint (PPO adds its prompt cursor and KL
        controller value). Every value must be exactly representable in
        float32 and derived from lockstep state."""
        return {}

    def _consistency_fingerprint(self) -> Dict[str, float]:
        """A few scalars that must be IDENTICAL on every host of a
        healthy SPMD run: global reductions over params + opt_state
        (computed in-graph, replicated — on multihost the all-reduce
        itself is part of the check), plus the step counter, a PRNG-key
        hash and any trainer cursors. Cheap by construction: one tiny
        jitted reduction and one small host fetch per check."""
        if self._fingerprint_fn is None:

            def fp(params, opt_state):
                def reduce_tree(tree):
                    tot = jnp.float32(0.0)
                    l1 = jnp.float32(0.0)
                    for leaf in jax.tree_util.tree_leaves(tree):
                        x = jnp.asarray(leaf)
                        if not jnp.issubdtype(x.dtype, jnp.floating):
                            continue
                        x = x.astype(jnp.float32)
                        tot = tot + jnp.sum(x)
                        l1 = l1 + jnp.sum(jnp.abs(x))
                    return tot, l1

                p_sum, p_l1 = reduce_tree(params)
                o_sum, o_l1 = reduce_tree(opt_state)
                return jnp.stack([p_sum, p_l1, o_sum, o_l1])

            from trlx_tpu.parallel.mesh import replicated_sharding

            self._fingerprint_fn = jax.jit(
                fp, out_shardings=replicated_sharding(self.mesh)
            )
        with self.mesh:
            vec = np.asarray(self._fingerprint_fn(self.params, self.opt_state))
        out = {
            "params_sum": float(vec[0]),
            "params_l1": float(vec[1]),
            "opt_sum": float(vec[2]),
            "opt_l1": float(vec[3]),
            "iter": float(self.iter_count),
            # key-data hash folded into float32's exact-integer range
            "rng": float(
                int(np.asarray(self._pack_rng(), np.uint64).sum()) % (1 << 20)
            ),
        }
        out.update(self._extra_fingerprint())
        # values ride the consensus gather as float32: fold everything
        # through it up front so local-vs-reference compares are exact
        return {k: float(np.float32(v)) for k, v in out.items()}

    def _maybe_check_consistency(self) -> None:
        """Every ``guardrails.consistency_every`` cycles: fingerprint
        the local state and compare it against the fleet consensus
        (``multihost.consensus``). Divergence — one host's values
        departing the agreed reference — trips the escalation ladder
        like any other health signal instead of letting the host drift
        until a shape error or silent reward collapse. The chaos
        ``host_divergence`` fault perturbs THIS host's view after the
        gather, so the single-host simulation detects it the same way a
        peer would in a real fleet."""
        every = self.guardrails.cfg.consistency_every
        if not self.guardrails.enabled or every <= 0:
            return
        self._consistency_counter += 1
        if self._consistency_counter % every:
            return
        straggler_detail = None
        if self.watchdog.enabled and mh.is_multihost():
            # soft stall path: while collectives still work, compare
            # heartbeat counters fleet-wide — a host whose beats lag the
            # fleet max is a straggler, named by host AND phase, and the
            # trip walks the unified guardrails ladder (the hard path —
            # a frozen loop — is the monitor thread's deadline abort)
            strag = mh.straggler_report(self.watchdog.phase_ages())
            if not strag.agree:
                straggler_detail = strag.detail
                self.guardrails.trip(
                    STALL_SIGNAL,
                    f"cross-host straggler at step {self.iter_count}: "
                    f"{strag.detail}",
                )
        local = self._consistency_fingerprint()
        result = mh.consensus(local, atol=self.guardrails.cfg.consistency_atol)
        if self.chaos is not None and self.chaos.consult("host_divergence"):
            local = self.chaos.perturb_fingerprint(local)
        detail = result.detail
        if result.agree:
            # same agreement predicate as the cross-host row compare
            # (mh.values_agree): identical-NaN state is a fleet-wide
            # health problem for the loss guards, not a divergence
            atol = self.guardrails.cfg.consistency_atol
            drifted = [
                f"{k}={local[k]!r} != consensus {result.reference[k]!r}"
                for k in sorted(local)
                if not mh.values_agree(
                    local[k], result.reference.get(k, float("nan")), atol
                )
            ]
            detail = "; ".join(drifted[:8])
        if not result.agree or detail:
            self.guardrails.trip(
                "consistency",
                f"cross-host state fingerprint diverged at step "
                f"{self.iter_count}: {detail or 'rows disagree'}",
            )
        # flight recorder: cross-host row at the consensus cadence —
        # the local phase wall/beat counters (the straggler-attribution
        # signal) land in the same correlated timeline as everything
        # else, so "which host/phase was behind" reads off one stream.
        # The straggler verdict is the REPORT's, not the fingerprint's
        # (a numeric state divergence already rides the `consistency`
        # guardrail trip above — labeling it a straggler would misname
        # state drift as slowness).
        self.obs.record_hosts(
            self.watchdog.phase_ages() if self.watchdog.enabled else {},
            straggler_detail,
        )
        # trainer-specific lockstep assertions at the same cadence (PPO:
        # the experience-transport consumer cursor via
        # multihost.cursor_consensus)
        self._extra_consistency_checks()

    def _extra_consistency_checks(self) -> None:
        """Subclass hook, run at the consistency-check cadence after the
        fingerprint consensus: extra cross-host agreement assertions
        whose disagreement should trip the ladder."""

    def _requeue_poisoned_batch(self) -> bool:
        """Hook: discard the current (poisoned) training batch and
        arrange for its source data to be replayed. Base trainers have
        no requeue-able store; PPO discards the rollout store and
        rewinds the prompt cursor."""
        return False

    def _reset_data_stream(self) -> None:
        """Hook: rebuild the training data stream from position zero so
        a subsequent load()'s cursor restore can fast-forward to an
        EARLIER position than the live one (streams only advance). PPO
        rebuilds its prompt iterator from the retained pipeline."""

    def _apply_lr_cut(self, factor: float) -> None:
        """Multiply the whole LR schedule by ``factor`` (cumulative in
        self._lr_scale, persisted in state.json). The optimizer is
        rebuilt around the scaled schedule; optimizer STATE carries over
        unchanged (same transform structure), and the jitted steps are
        dropped so the next dispatch traces the new schedule in."""
        self._lr_scale *= float(factor)
        self._rebuild_optimizer()
        logger.warning(
            "guardrails: learning rate cut by %g (cumulative scale %g)",
            factor, self._lr_scale,
        )

    def _assemble_optimizer(self, opt_cfg, sched_cfg):
        """(tx, schedule) from configs, with the freeze mask chained in
        — the ONE place the optimizer is assembled (__init__ and the
        guardrail rebuild must never drift apart)."""
        tx, schedule = build_optimizer(opt_cfg, sched_cfg)
        if hasattr(tx, "fused_apply"):
            # fused optimizers write params directly (no updates tree to
            # chain a mask into); _step_update streams the mask through
            # fused_apply instead
            pass
        elif self._update_mask is not None:
            # the step runs on the trainable view, where the mask is all
            # ones but for the leaves that fell back to a whole walk
            tx = optax.chain(tx, _mask_updates(
                trainable_view.view_mask(self._update_mask, self._view)))
        return tx, schedule

    def _rebuild_optimizer(self) -> None:
        okw = dict(self.config.optimizer.kwargs)
        skw = dict(self.config.scheduler.kwargs)
        if self._lr_scale != 1.0:
            okw["lr"] = okw["lr"] * self._lr_scale
            for k in ("eta_min", "final_lr"):
                # scale the schedule floor too, so the cut scales the
                # whole curve instead of pinning it to the old floor
                if k in skw:
                    skw[k] = skw[k] * self._lr_scale
        self.tx, self.schedule = self._assemble_optimizer(
            dataclasses.replace(self.config.optimizer, kwargs=okw),
            dataclasses.replace(self.config.scheduler, kwargs=skw),
        )
        self._train_step = None
        self._fused_train_step = None

    def _rollback_to_last_good(self) -> bool:
        """Auto-rollback: restore the newest committed resumable
        checkpoint — params, opt state, iter_count, PRNG key, KL
        controller / running moments and the prompt cursor (untrained
        prompts replay) — exactly as a process relaunch would, but
        in-process, losing at most checkpoint_interval steps. Commits
        are health-gated, so "latest resumable" is also "last good"."""
        def discover():
            path = self.ckpt_manager.latest_resumable()
            if mh.is_multihost():
                # stale shared-filesystem views must not pick different
                # checkpoints per host: process 0's discovery wins
                path = mh.allgather_object(path)[0]
            return path

        path = discover()
        if path is None:
            # nothing to restore: leave the live data stream UNTOUCHED
            # (resetting it here would clobber the prompt cursor of a
            # run that keeps training)
            logger.error(
                "guardrails: rollback requested but no resumable "
                "checkpoint exists under %s — continuing without "
                "rollback (the ladder will escalate if the run stays "
                "unhealthy)", self.config.train.checkpoint_dir,
            )
            return False
        self._abandon_prefetch()
        self._reset_data_stream()
        while True:
            logger.warning(
                "guardrails: auto-rollback to %s (discarding the diverged "
                "live state at step %d)", path, self.iter_count,
            )
            try:
                self.load(path)
                break
            except CheckpointCorruptError as e:
                # load() already quarantined the directory (renamed
                # *.corrupt), so re-discovery cannot hand it back:
                # fall back to the previous committed step instead of
                # aborting on poison
                logger.error(
                    "guardrails: rollback target was corrupt and has "
                    "been quarantined (%s); falling back to the "
                    "previous committed checkpoint", e,
                )
                path = discover()
                if path is None:
                    # every candidate was poison: nothing restorable.
                    # The data stream was already rebuilt from zero (a
                    # load was expected to fast-forward it), so the
                    # continuing run replays prompts from the stream
                    # start — cursor and stream stay self-consistent,
                    # and the alternative was crashing on poison.
                    logger.error(
                        "guardrails: no earlier resumable checkpoint "
                        "remains after quarantine — continuing without "
                        "rollback; the prompt stream was rebuilt from "
                        "zero, so subsequent cycles replay prompts",
                    )
                    return False
        # the restored arrays are fresh buffers: drop the jitted steps
        # whose output shardings were pinned to the donated originals
        self._train_step = None
        self._fused_train_step = None
        self._bad_steps = 0
        self.guardrails.notify_rollback(self.iter_count)
        return True

    def learn(self):
        """The training loop (parity: reference learn() :518-651)."""
        # memory doctor: admission control BEFORE any compile — an
        # over-budget config dies here with an itemized per-phase plan
        # instead of after a long compile (train.memory.preflight).
        # Deliberately before preemption.install(): a rejection must
        # not leak process-global signal handlers bound to a trainer
        # that never trained.
        self._memory_preflight()
        self.preemption.install()
        # arm the hang doctor for the duration of the loop (no-op when
        # train.watchdog is unset): phase heartbeats are already flowing
        # from the beat sites; this starts the monitor thread that
        # compares them against the deadlines
        self.watchdog.start()
        # ... and the memory doctor's HBM watermark sampler (no-op on
        # backends without memory_stats; default-off = no thread)
        self.memdoctor.sampler.start()
        # flight recorder: stamp provenance + open the first cycle
        # (resume keeps the restored run_id, so the stream stays one
        # correlated timeline across relaunches)
        self.obs.set_param_count(tree_param_count(self.params))
        self.obs.start(
            trainer=type(self).__name__,
            step=self.iter_count,
            total_steps=self.config.train.total_steps,
            batch_size=self.config.train.batch_size,
            seq_length=self.config.train.seq_length,
            mesh={ax: int(s) for ax, s in self.mesh.shape.items()},
            decode_impl=self._decode_impl(),
        )
        try:
            # serving frontend (train.serve.*): external requests ride
            # the engine lanes between training dispatches from here
            # on. INSIDE the try: a failed start (ineligible model,
            # transport bind error) must not leak the signal handlers
            # and monitor threads armed above — the same bug class the
            # memory-doctor preflight hardening fixed.
            self._serve_start()
            return self._learn()
        finally:
            # serving teardown FIRST: still-queued requests get a
            # cancelled result while the transport is certainly alive.
            # GUARDED: a teardown failure (transport outage mid-close)
            # must not skip the watchdog/preemption/tracker teardowns
            # below or mask the training exception.
            try:
                self._serve_close()
            except Exception:
                logger.exception("serve teardown failed (continuing)")
            self.memdoctor.sampler.stop()
            self.watchdog.stop()
            self.preemption.uninstall()
            # rollout phases defer their stats behind an async device->host
            # copy; flush even when learn() exits straight after a rollout
            # (total_steps hit before the next train step, or an exception)
            # so the final chunk's stats always reach the tracker
            self._finish_rollout_stats()
            # a deferred fused block may still be pending on an abnormal
            # exit (preemption/exception): flush it for the tracker, but
            # don't let the NaN-abort guard mask the live control flow
            self._finish_train_stats(suppress_abort=True)
            # an in-flight cross-cycle rollout prefetch never trains once
            # learn() exits: drop it and rewind its prompt cursor so a
            # resumed run replays those prompts
            self._abandon_prefetch()
            # flight recorder: close the open cycle and refresh the
            # flight-dir telemetry snapshot (after the stat flushes
            # above, so the final cycle's numbers are in it)
            self.obs.finish()
            # tracker teardown LAST among metric consumers — close()
            # re-drains any deferred stats the flushes above missed
            # (none in this ordering; the drain is the backstop) and
            # then flushes/releases the backends
            self.tracker.close()
            # external producer fleets (ppo.fleet.*): signal clean
            # finish when the budget is done, leave the fleet attached
            # for the relaunch handshake otherwise
            self._shutdown_producers()

    def _learn(self):
        logger.info("Starting training")
        # the relaunch loop re-runs a COMPLETED job's command line: bail
        # before prepare_learning, which for PPO would pay a full rollout
        # (generation + reward scoring) for nothing. The run-derived
        # budget from state.json covers store-limited PPO runs, gated on
        # an unchanged config total (raising total_steps means the user
        # wants to continue past the old budget).
        restored_done = (
            self._restored_total_steps is not None
            and self.iter_count >= self._restored_total_steps
            and self.config.train.total_steps == self._restored_config_total_steps
        )
        if self.iter_count > 0 and (
            self.iter_count >= self.config.train.total_steps or restored_done
        ):
            logger.info(
                "restored iter_count %d already covers the step budget "
                "(total_steps=%d%s); nothing to train", self.iter_count,
                self.config.train.total_steps,
                "" if self._restored_total_steps is None
                else f", run-derived={self._restored_total_steps}",
            )
            return {}
        self.prepare_learning()
        if self._should_stop(force=True):
            # preemption landed during prepare_learning (PPO: the first
            # rollout, possibly abandoned part-way) — checkpoint and
            # exit before paying the initial evaluation
            self._preemption_exit()
            return {}

        if self.iter_count > 0:
            # resumed run: continue from the restored step — replaying
            # from 0 with a restored optimizer state was the old (silent)
            # failure mode. The initial evaluation is skipped so tracker
            # step indices stay strictly monotonic across the restart.
            logger.info(
                "Resuming training at step %d/%d (best_reward=%s)",
                self.iter_count, self.total_steps,
                significant(self.best_reward),
            )
            results: Dict[str, Any] = {}
            if self.iter_count >= self.total_steps:
                logger.info(
                    "restored iter_count %d already >= total_steps %d; "
                    "nothing to train", self.iter_count, self.total_steps,
                )
                return results
        else:
            results = self.evaluate()
            self._tracker_log(results, step=self.iter_count)

        if self._train_step is None:
            self._train_step = self.make_train_step()

        clock = Clock()
        for _ in range(self.config.train.epochs):
            # epoch-top check catches a preemption that landed during
            # rollout collection / evaluation (PPO abandons the rollout
            # and falls through to here with a short or empty store)
            if self._should_stop(force=True):
                self._preemption_exit()
                return results
            # serving tick at the cycle boundary: requests that arrived
            # during the fused optimization block are served before the
            # next training dispatch
            self._serve_tick(self.iter_count)
            fused_src = (
                self._fused_epoch_batch()
                if self.config.train.fused_inner_loop
                else None
            )
            if fused_src is not None:
                results, done = self._learn_fused(fused_src, results)
                if done:
                    return results
                self.post_epoch_callback()
                continue
            # falling back to the per-step loop (empty/streaming store):
            # a still-deferred fused block from an earlier epoch must log
            # before this loop emits newer step indices
            self._finish_train_stats()
            guard_break = False  # ladder consumed this epoch's data
            cycle_steps0 = self.iter_count  # flight-recorder cycle span
            for _ in range(self.n_inner_epochs):
                train_dataloader = self.create_train_dataloader()
                for batch in train_dataloader:
                    if self._should_stop():
                        self._preemption_exit()
                        return results
                    if self._train_step is None:
                        # a guardrail lr_cut dropped the jitted step
                        # mid-epoch (the new schedule must trace in)
                        self._train_step = self.make_train_step()
                    device_batch = self.place_batch(batch)
                    if self.chaos is not None and self.chaos.consult("nan_loss"):
                        # chaos: poison THIS step's batch (per-step loop
                        # counterpart of the fused-block site — a
                        # trainer runs exactly one of the two paths, so
                        # the consult counter stays deterministic; this
                        # is what brings the ILQL/SFT/RFT per-step
                        # trainers under the chaos umbrella)
                        device_batch = poison_batch(device_batch)
                    forward_time = clock.tick()
                    self.watchdog.beat(
                        "train_step", "start", step=self.iter_count
                    )
                    # memory-doctor envelope (per-step counterpart of
                    # the fused-block one; the oom_fused_block chaos
                    # site doubles for this path like nan_loss does —
                    # a trainer runs exactly one of the two)
                    oom_skip = False
                    for _attempt in range(self._oom_retry_budget()):
                        try:
                            if self.chaos is not None and self.memdoctor.enabled:
                                self.chaos.oom("oom_fused_block")
                            if self._train_step is None:
                                self._train_step = self.make_train_step()
                            with self.mesh:
                                self.params, self.opt_state, loss, stats = self._train_step(
                                    self.params, self.opt_state, device_batch
                                )
                            break
                        except Exception as e:
                            if not (self.memdoctor.enabled and is_oom(e)):
                                raise
                            if self._handle_oom(e, "train_step") == "skip":
                                oom_skip = True
                                break
                    else:
                        raise RuntimeError(
                            "memory doctor: train step still "
                            "RESOURCE_EXHAUSTED after exhausting the "
                            "degradation retry budget"
                        )
                    if oom_skip:
                        # rollback consumed this step's data source —
                        # restart from the epoch top like a guardrail
                        # rollback does
                        self.watchdog.beat(
                            "train_step", "end", step=self.iter_count
                        )
                        guard_break = True
                        break
                    if self.chaos is not None:
                        if self.chaos.consult("sigterm"):
                            # chaos: preemption lands while the device is
                            # mid-step (dispatch is async) — same worst
                            # moment the fused path injects
                            import signal as _signal

                            os.kill(os.getpid(), _signal.SIGTERM)
                        # chaos: host wedges in the step's device sync
                        self.chaos.stall("stall_collective")
                    loss = to_scalar(loss)  # sync point: step is done
                    self.watchdog.beat(
                        "train_step", "end", step=self.iter_count
                    )
                    step_time = clock.tick()
                    bad = self._guard_bad_loss(loss)
                    # per-step counterpart of the fused path's
                    # once-per-cycle watermark consumption
                    self._check_memory_watermark()
                    if self.guardrails.enabled:
                        # unfused loop: one step = one watchdog cycle
                        self.guardrails.observe_train(
                            step=self.iter_count, loss=loss,
                            grad_norm=(
                                to_scalar(stats["losses/grad_norm"])
                                if "losses/grad_norm" in stats else None
                            ),
                        )
                        if self._run_guardrail_ladder():
                            # rollback/requeue: this dataloader's source
                            # is gone — restart from the epoch top
                            guard_break = True
                            break
                    if bad:
                        # poisoned update was skipped device-side: the
                        # step index does not advance and nothing is
                        # logged for it (the next good step keeps the
                        # tracker's step sequence contiguous)
                        continue
                    stats = {
                        k: to_scalar(v)
                        for k, v in stats.items()
                        if np.ndim(v) == 0
                    }
                    stats["time/step"] = step_time
                    # jit fuses fwd+bwd+update, so a per-step split does not
                    # exist; optionally measure a forward-only pass once
                    # (static shapes => constant cost) to fill the
                    # reference's time/forward & time/backward keys honestly
                    # skip the split on the first step of each batch shape:
                    # that step_time includes the train-step compile, which
                    # would otherwise be booked entirely under time/backward
                    shape_key = _batch_shape_key(device_batch)
                    if self.config.train.timing_split and (
                        shape_key in self._seen_step_shapes
                    ):
                        fwd_time = self._measure_forward(device_batch)
                        stats["time/forward"] = fwd_time
                        stats["time/backward"] = max(step_time - fwd_time, 0.0)
                    self._seen_step_shapes.add(shape_key)
                    stats["learning_rate_group_0"] = float(
                        self.schedule(self.iter_count)
                    )
                    self.iter_count += 1

                    ckpt_every = self.config.train.checkpoint_interval
                    if ckpt_every > 0 and (
                        self.iter_count % ckpt_every == 0
                        or self.iter_count >= self.total_steps
                    ):
                        self._save_checkpoint(self._checkpoint_tag())

                    if (
                        self.iter_count % self.config.train.eval_interval == 0
                        or self.iter_count >= self.total_steps
                    ):
                        results = self.evaluate()
                        stats.update(results)
                        self._maybe_save_best(stats)

                    desc = " | ".join(
                        f"{k}: {v:.2f}"
                        for k, v in stats.items()
                        if k.startswith("losses/") or k == "loss"
                    )
                    logger.info("[step %d/%d] %s", self.iter_count, self.total_steps, desc)
                    # pending rollout stats carry an earlier step index:
                    # flush them first so tracker steps stay monotonic
                    self._finish_rollout_stats()
                    self._tracker_log(stats, step=self.iter_count)

                    if self.iter_count >= self.total_steps:
                        return results
                if guard_break:
                    break
                self.post_backward_callback()
            # per-step loop: one completed optimization cycle = one
            # staleness unit (the fused path counts one per block — both
            # count one version per pass over the cycle's data)
            if not guard_break:
                self._policy_version += 1
            # flight recorder: per-step-loop counterpart of the fused
            # path's cycle boundary (one cycle per inner-epoch pass)
            self.obs.end_cycle(
                step=self.iter_count, policy_version=self._policy_version,
                n_steps=self.iter_count - cycle_steps0,
            )
            self.post_epoch_callback()
        # epoch exhaustion can end BELOW total_steps (a NaN-skipped step
        # consumes its batch without advancing iter_count, and small
        # datasets simply run out of epochs): commit whatever progress
        # exists rather than leaving up to checkpoint_interval steps of
        # training only in memory
        if self.iter_count > 0:
            self._commit_final_checkpoint("epoch budget exhausted")
        return results

    # ------------------------------------------------------------------
    # checkpointing
    # ------------------------------------------------------------------

    def _state_tree(self) -> Dict:
        return {"params": self.params, "opt_state": self.opt_state}

    def _shutdown_producers(self) -> None:
        """Subclass hook, called from learn()'s ``finally``: tear down
        any external rollout-producer fleet when the run is over for
        good, and leave it alive for re-attach when this exit is a
        preemption / stall / crash a supervisor will relaunch."""

    def _extra_state(self) -> Dict[str, Any]:
        """Subclass hook: extra JSON-serializable resumable state (KL
        controller value, data cursors, ...) merged into state.json."""
        return {}

    def _restore_extra_state(self, state: Dict[str, Any]) -> None:
        """Subclass hook: restore what `_extra_state` saved."""

    def _pack_rng(self) -> List[int]:
        try:
            data = jax.random.key_data(self.rng)
        except Exception:  # old-style raw uint32 key array
            data = self.rng
        return np.asarray(data).astype(np.uint32).tolist()

    def _unpack_rng(self, data) -> None:
        arr = jnp.asarray(np.asarray(data, np.uint32))
        try:
            if jnp.issubdtype(self.rng.dtype, jax.dtypes.prng_key):
                arr = jax.random.wrap_key_data(arr)
        except Exception:
            pass
        self.rng = arr

    def save(self, directory: Optional[str] = None) -> None:
        """Full training state via Orbax + state.json (parity: reference
        save :309-326 / accelerator.save_state).

        state.json carries everything needed to CONTINUE the run rather
        than replay it: iter_count, best_reward, the trainer PRNG key,
        the eval counter and per-trainer cursors (_extra_state). It is
        written to a temp file and os.replace'd — a preemption mid-save
        can never leave a truncated state.json shadowing a good one."""
        import orbax.checkpoint as ocp

        directory = os.path.abspath(directory or self.config.train.checkpoint_dir)
        ckptr = ocp.PyTreeCheckpointer()
        # orbax writes distributed arrays collectively: every process
        # calls save (each persists its shards); only process 0 writes
        # the scalar metadata
        ckptr.save(
            os.path.join(directory, "state"), self._state_tree(), force=True
        )
        if mh.is_main():
            atomic_json_write(
                os.path.join(directory, "state.json"),
                self._resume_state_dict(),
            )
            self._write_topology_manifest(directory)

    def _resume_state_dict(self) -> Dict[str, Any]:
        """The state.json contents: everything needed to CONTINUE the
        run rather than replay it. The ONE builder — the checkpoint
        save and the hang doctor's host-RAM shadow both use it, so an
        emergency snapshot resumes exactly like a regular checkpoint."""
        state = {
            "iter_count": self.iter_count,
            "best_reward": (
                self.best_reward if np.isfinite(self.best_reward) else None
            ),
            "nth_evaluation": self.nth_evaluation,
            "rng_key": self._pack_rng(),
            # cumulative guardrail LR-cut factor: a resumed (or
            # rolled-back) run re-applies the cut schedule exactly
            "lr_scale": self._lr_scale,
            # run-derived budget (PPO: min of config and store size):
            # lets a same-config relaunch of a COMPLETED run bail
            # before paying a rollout. A preemption-abandoned rollout
            # truncates the store, so the just-derived total_steps
            # UNDERSTATES the real budget — persisting it would make
            # every later relaunch bail as "completed"; carry the
            # restored values forward instead.
            "total_steps": (
                self._restored_total_steps
                if self._rollout_abandoned else self.total_steps
            ),
            "config_total_steps": (
                self._restored_config_total_steps
                if self._rollout_abandoned
                else self.config.train.total_steps
            ),
        }
        if self.memdoctor.enabled:
            # memory-doctor degradation level (pool shrinks / grad-accum
            # factor / remat escalation): committed INSIDE the atomic
            # state.json so a supervise.py relaunch and trainer.load()
            # resume already-degraded instead of re-OOMing at the
            # original sizes (verify_ckpt.py reports it)
            state["memory_degrade"] = self.memdoctor.degrade_state()
        if self.obs.active:
            # flight-recorder correlation state: the run_id + telemetry
            # run totals, so a resume appends to the SAME correlated
            # stream (ids stable across restart) and the trajectory
            # point keeps covering the whole run. Omitted when obs is
            # disabled — verify_ckpt.py must not advertise a stream
            # that was never written.
            state["obs"] = self.obs.state_dict()
        if self.guardrails.enabled:
            # bounded guardrail trip tail, committed in the same atomic
            # state.json: the post-resume event stream (and
            # verify_ckpt.py) keeps the pre-restart trip record
            state["guardrail_trips"] = self.guardrails.trip_tail()
        state.update(self._extra_state())
        return state

    def _topology_manifest(self) -> Dict[str, Any]:
        """The world that saved this checkpoint: mesh axis sizes, host
        and data-group counts, the global batch, and every state leaf's
        GLOBAL shape + dtype. Global shapes are mesh-independent, so a
        resume onto a different topology validates architecture against
        them (a shape mismatch is a model change, not a topology
        change) and reshards everything else onto the current mesh."""
        leaves = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            self._state_tree()
        )[0]:
            key = jax.tree_util.keystr(path)
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = str(np.asarray(leaf).dtype if not hasattr(leaf, "dtype")
                        else leaf.dtype)
            leaves[key] = {"shape": list(shape), "dtype": dtype}
        return {
            "format": 1,
            "mesh": {ax: int(s) for ax, s in self.mesh.shape.items()},
            "process_count": mh.process_count(),
            "data_group_count": mh.data_group_count(self.mesh),
            "global_batch_size": int(self.config.train.batch_size),
            "leaves": leaves,
        }

    def _write_topology_manifest(self, directory: str) -> None:
        atomic_json_write(
            os.path.join(directory, TOPOLOGY_MANIFEST),
            self._topology_manifest(),
        )

    def _validate_topology(self, directory: str) -> None:
        """Elastic-resume gate, run BEFORE the orbax restore: compare
        the checkpoint's topology manifest against the live run.

        - per-leaf GLOBAL shape/dtype mismatches are an ARCHITECTURE
          change and always a hard error (restoring would silently
          broadcast/garble leaves);
        - mesh / host-count / data-group differences are a TOPOLOGY
          change: logged and allowed (the restore reshards onto the
          current mesh; the global PRNG key restores unchanged — it is
          host-independent by construction — and the PPO prompt stream
          is re-split via the group-invariant chunk schedule) unless
          ``train.elastic.allow_topology_change`` is false.
        Pre-elastic checkpoints (no manifest) restore as before, with a
        note."""
        fp = os.path.join(directory, TOPOLOGY_MANIFEST)
        if not os.path.isfile(fp):
            logger.info(
                "checkpoint %s has no topology manifest (pre-elastic "
                "save): resuming without topology validation", directory,
            )
            return
        with open(fp) as f:
            saved = json.load(f)
        live = self._topology_manifest()
        mismatched = []
        saved_leaves = saved.get("leaves", {})
        for key, meta in live["leaves"].items():
            got = saved_leaves.get(key)
            if got is None:
                mismatched.append(f"{key}: missing from checkpoint")
            elif list(got["shape"]) != meta["shape"] or got["dtype"] != meta["dtype"]:
                mismatched.append(
                    f"{key}: checkpoint {got['shape']}/{got['dtype']} vs "
                    f"live {meta['shape']}/{meta['dtype']}"
                )
        extra = set(saved_leaves) - set(live["leaves"])
        if extra:
            mismatched.append(f"checkpoint-only leaves: {sorted(extra)[:4]}")
        if mismatched:
            raise ValueError(
                f"checkpoint {directory} does not match the live model "
                f"ARCHITECTURE ({len(mismatched)} leaf mismatches; first: "
                f"{mismatched[0]}) — topology-change resume reshards the "
                "same global arrays onto a new mesh, it cannot convert "
                "between different models/optimizers"
            )
        topo_keys = ("mesh", "process_count", "data_group_count")
        changed = {
            k: (saved.get(k), live[k])
            for k in topo_keys
            if saved.get(k) != live[k]
        }
        if changed:
            if not self.elastic.allow_topology_change:
                raise ValueError(
                    f"checkpoint {directory} was saved under a different "
                    f"topology ({changed}) and "
                    "train.elastic.allow_topology_change is false"
                )
            logger.warning(
                "elastic resume: checkpoint %s was saved under a "
                "different topology (%s) — restoring onto the current "
                "mesh (params/opt-state resharded; PRNG key restored "
                "unchanged; data cursors re-split)", directory,
                "; ".join(
                    f"{k}: {old} -> {new}" for k, (old, new) in changed.items()
                ),
            )
        if saved.get("global_batch_size") != live["global_batch_size"]:
            logger.warning(
                "elastic resume: global batch size changed (%s -> %s); "
                "iter_count-derived schedules (LR, shuffles) keep their "
                "step semantics but cover different sample counts",
                saved.get("global_batch_size"), live["global_batch_size"],
            )

    def load(
        self,
        directory: Optional[str] = None,
        quarantine_corrupt: bool = True,
    ) -> None:
        import orbax.checkpoint as ocp

        directory = os.path.abspath(directory or self.config.train.checkpoint_dir)
        # integrity gate FIRST (before any state mutation): a shard
        # flipped by bad storage must never reach params. On mismatch
        # the checkpoint is quarantined (*.corrupt) and
        # CheckpointCorruptError propagates — the auto-resume and
        # auto-rollback paths catch it and fall back to the previous
        # committed step. ``quarantine_corrupt=False`` (user-pinned
        # explicit paths) raises without the rename.
        if self.elastic.verify_integrity:
            verify_or_quarantine(directory, do_quarantine=quarantine_corrupt)
        # then the elastic-resume gate: global-shape/dtype (architecture)
        # validation and the topology-change decision, also pre-mutation
        self._validate_topology(directory)
        ckptr = ocp.PyTreeCheckpointer()
        template = self._state_tree()
        # restore WITH the live template's shardings (RestoreArgs):
        # orbax then materializes each leaf directly onto the CURRENT
        # mesh — the topology-change path — instead of reading the
        # saved run's sharding file (which references a mesh that may
        # no longer exist) and deferring the reshard to us
        from orbax.checkpoint import checkpoint_utils

        restore_args = checkpoint_utils.construct_restore_args(template)
        restored = ckptr.restore(
            os.path.join(directory, "state"), item=template,
            restore_args=restore_args,
        )

        # Re-materialize the restored leaves as fresh XLA-ALLOCATED
        # buffers on the live arrays' shardings. The train step DONATES
        # params/opt_state, and restored arrays can be host-memory
        # backed (orbax restores to numpy; device_put of host memory
        # zero-copies on CPU) — donating such a buffer hands XLA memory
        # it does not own, observed under the chaos harness as
        # post-rollback NaN params and glibc "corrupted double-linked
        # list" aborts. A jitted identity copy cannot alias its
        # (non-donated) inputs, so its outputs are genuinely
        # XLA-allocated; one extra state copy per resume/rollback is
        # the price.
        live = {"params": template["params"], "opt_state": template["opt_state"]}
        raw = {"params": restored["params"], "opt_state": restored["opt_state"]}

        def placed(tmpl, value):
            if isinstance(tmpl, jax.Array):
                if isinstance(value, jax.Array):
                    # already device-resident (restore_args placed it on
                    # the live mesh); the jitted copy below still
                    # re-materializes it into XLA-owned buffers
                    return value
                return jax.device_put(np.asarray(value), tmpl.sharding)
            return value

        with self.mesh:
            staged = jax.tree_util.tree_map(placed, live, raw)
            shardings = jax.tree_util.tree_map(
                lambda t, v: t.sharding if isinstance(t, jax.Array) else None,
                live, raw,
            )
            restored_state = jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t),
                out_shardings=shardings,
            )(staged)
        self.params = restored_state["params"]
        self.opt_state = restored_state["opt_state"]
        state_fp = os.path.join(directory, "state.json")
        if not os.path.exists(state_fp):
            # a corrupt/legacy checkpoint must not masquerade as a fresh
            # run: params were restored above, but the step counter and
            # reward history are unknown — resume would restart from 0
            logger.warning(
                "checkpoint %s has no state.json: params/opt_state were "
                "restored but iter_count/best_reward are unknown — "
                "treating as step 0 (legacy layout or a corrupted save)",
                directory,
            )
            return
        with open(state_fp) as f:
            state = json.load(f)
        self.iter_count = state.get("iter_count", 0)
        best = state.get("best_reward")
        self.best_reward = float(best) if best is not None else -float("inf")
        self.nth_evaluation = state.get("nth_evaluation", 0)
        scale = float(state.get("lr_scale", 1.0))
        if scale != self._lr_scale:
            self._lr_scale = scale
            self._rebuild_optimizer()
        if state.get("rng_key") is not None:
            self._unpack_rng(state["rng_key"])
        self._restored_total_steps = state.get("total_steps")
        self._restored_config_total_steps = state.get("config_total_steps")
        self._restore_memory_degrade(state.get("memory_degrade"))
        # flight recorder: adopt the saved run_id + telemetry totals
        # (correlation ids stable across resume) and the guardrail trip
        # tail, then mark the restore in the stream itself
        self.obs.load_state_dict(state.get("obs"))
        self.guardrails.load_trip_tail(state.get("guardrail_trips"))
        self.obs.record(
            "restore", path=os.path.basename(directory),
            to_step=self.iter_count,
        )
        self._restore_extra_state(state)

    def _restore_memory_degrade(self, saved: Optional[Dict[str, Any]]) -> None:
        """Adopt a checkpoint's persisted memory-doctor degradation.
        A DEGRADED checkpoint exists because the original sizes already
        OOMed — resuming it under a config that silently un-degrades it
        (doctor disabled) would re-OOM at exactly those sizes, so that
        fails LOUDLY unless ``train.memory.accept_undegrade`` asserts
        the environment changed. The merge is by max (monotonic), so a
        guardrail rollback restoring an older state.json can never
        un-degrade the live run either."""
        if not saved or not is_degraded_record(saved):
            return
        if self.memdoctor.cfg.accept_undegrade:
            logger.warning(
                "memory doctor: checkpoint carries degradation (%s) but "
                "train.memory.accept_undegrade is set — resuming at the "
                "ORIGINAL sizes; you are asserting they fit now",
                saved,
            )
            return
        if not self.memdoctor.enabled:
            raise ValueError(
                "this checkpoint was committed DEGRADED by the memory "
                f"doctor ({saved}) — the original sizes already OOMed — "
                "but train.memory is disabled in the resuming config, "
                "which would silently un-degrade it and re-OOM. Enable "
                "train.memory.enabled to resume degraded, or set "
                "train.memory.accept_undegrade: true to assert the "
                "original sizes fit now (e.g. after moving to larger "
                "devices)"
            )
        self.memdoctor.restore(saved)
        self._apply_degradation()
        logger.warning(
            "memory doctor: resumed degraded — %s", self.memdoctor.describe()
        )

    def save_pretrained(self, directory: Optional[str] = None) -> None:
        """Deploy artifact: HF-format export of the base model when the
        architecture supports it, else an Orbax params dump (parity:
        reference save_pretrained :285-307)."""
        directory = os.path.abspath(
            directory
            or os.path.join(self.config.train.checkpoint_dir, "hf_model")
        )
        os.makedirs(directory, exist_ok=True)
        base = self.params.get("base", self.params)
        # all processes join the gather (collective); process 0 writes
        base = mh.gather_params(base)
        # auxiliary heads (value / Q) ride alongside the deploy artifact so
        # an ILQL/PPO policy reloads losslessly (the HF export itself stays
        # base-only for from_pretrained parity, reference :526-553)
        aux = {k: v for k, v in self.params.items() if k != "base"}
        if aux:
            aux = mh.gather_params(aux)
            import orbax.checkpoint as ocp

            # orbax save is COLLECTIVE (internal sync_global_devices):
            # every process must call it, even though only the primary
            # writes
            ocp.PyTreeCheckpointer().save(
                os.path.join(directory, "aux"), aux, force=True
            )
            # trained adapters ALSO export in the HF-peft layout
            # (adapter_config.json + adapter_model.safetensors), so a
            # LoRA trained here serves through HF peft and reloads via
            # ModelConfig.peft_config=<dir> (ref modeling_base.py:347-353)
            from trlx_tpu.models.peft import ADAPTER_KEYS, save_peft_adapter

            adapters = {k: aux[k] for k in ADAPTER_KEYS if k in aux}
            if adapters and getattr(self, "_peft_cfg", None) and mh.is_main():
                try:
                    save_peft_adapter(
                        directory, adapters, self._peft_cfg, self.model.cfg,
                        getattr(self, "model_type", None),
                    )
                except Exception as e:  # keep the orbax artifact authoritative
                    logger.warning("HF-peft adapter export failed: %s", e)
        model_type = getattr(self, "model_type", None)
        exported = False
        if (
            model_type is not None
            and getattr(self, "_hf_config_path", None)
            and mh.is_main()
        ):
            try:
                import transformers

                hf_config = transformers.AutoConfig.from_pretrained(self._hf_config_path)
                save_pretrained_hf(base, self.model.cfg, model_type, hf_config, directory)
                exported = True
            except Exception as e:
                logger.warning("HF export failed (%s); saving orbax params", e)
        # all processes must agree on the fallback (the orbax save below
        # is collective)
        exported = mh.broadcast_flag(exported)
        if not exported:
            import dataclasses

            import orbax.checkpoint as ocp

            ocp.PyTreeCheckpointer().save(
                os.path.join(directory, "params"), base, force=True
            )
            if mh.is_main():
                tcfg = {
                    k: v
                    for k, v in dataclasses.asdict(self.model.cfg).items()
                    if k not in ("dtype", "param_dtype") and v is not None
                }
                arch_key = (
                    "seq2seq"
                    if self.config.model.model_arch_type == "seq2seq"
                    else "transformer"
                )
                with open(os.path.join(directory, "trlx_tpu_config.json"), "w") as f:
                    json.dump({arch_key: tcfg, "model_type": model_type}, f)
        if mh.is_main() and hasattr(self.tokenizer, "save_pretrained"):
            self.tokenizer.save_pretrained(directory)
        # wait out process 0's plain-file writes: racing ahead would let
        # a process enqueue device collectives that interleave with the
        # laggard's. With the hang doctor armed the wait is bounded: a
        # dead peer raises BarrierTimeout instead of hanging forever.
        mh.timed_barrier(
            "save_pretrained",
            self.watchdog.cfg.barrier_timeout_s if self.watchdog.enabled else 0,
        )


# ---------------------------------------------------------------------------
# update masking (layer freezing)
# ---------------------------------------------------------------------------


def _mask_updates(mask_tree) -> optax.GradientTransformation:
    """Multiply updates elementwise by a broadcastable {0,1} mask (None:
    a leaf with nothing to mask)."""

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        masked = jax.tree_util.tree_map(
            lambda u, m: u if m is None else u * jnp.asarray(m, u.dtype), updates, mask_tree
        )
        return masked, state

    return optax.GradientTransformation(init_fn, update_fn)
