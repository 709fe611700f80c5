"""`trlx_tpu.train` — the single user entry point.

Parity: /root/reference/trlx/trlx.py:15-143 — same signature and the same
argument-driven algorithm selection: `reward_fn` -> online PPO,
`rewards`/`dataset` -> offline ILQL, otherwise SFT.

Beyond the reference's four algorithms the registry also carries the
critic-free preference-RL pair: `train.trainer="TPUGRPOTrainer"` runs
GRPO through the online branch (same `reward_fn` + `prompts` contract
as PPO, riding the shared experience core), and
`train.trainer="TPUDPOTrainer"` runs DPO through the offline branch
with `samples` as (prompt, chosen, rejected) preference triples and no
`rewards`.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple, Union

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.default_configs import (
    default_ilql_config,
    default_ppo_config,
    default_sft_config,
)
from trlx_tpu.utils import logging, set_seed
from trlx_tpu.utils.compile_cache import enable_compile_cache
from trlx_tpu.utils.loading import get_pipeline, get_trainer

logger = logging.get_logger(__name__)


def train(
    model_path: Optional[str] = None,
    reward_fn: Optional[Callable[[List[str], List[str], List[str]], List[float]]] = None,
    dataset: Optional[Iterable[Tuple[str, float]]] = None,
    samples: Optional[List[str]] = None,
    rewards: Optional[List[float]] = None,
    prompts: Optional[Union[List[str], List[Dict[str, Any]]]] = None,
    eval_prompts: Optional[Union[List[str], List[Dict[str, Any]]]] = None,
    metric_fn: Optional[Callable[[List[str], List[str], List[str]], Dict[str, List[float]]]] = None,
    config: Optional[TRLConfig] = None,
    stop_sequences: Optional[List[str]] = None,
):
    """Run online RL (PPO), offline RL (ILQL) or supervised fine-tuning,
    selected by which arguments are provided.

    reward_fn(samples, prompts, outputs, **metadata) -> list of scalar
    rewards drives online training; (samples, rewards) drive offline
    training; samples alone drive SFT.
    """
    if config is None:
        warnings.warn(
            "Passing the `config` argument implicitly is depreciated, use or"
            "adapt some from `trlx_tpu/data/default_configs.py` instead"
        )
        if reward_fn:
            config = default_ppo_config()
        elif rewards:
            config = default_ilql_config()
        else:
            config = default_sft_config()

    set_seed(config.train.seed)
    # before the trainer exists: its constructor already compiles
    enable_compile_cache()

    if dataset is not None:
        warnings.warn("the `dataset` argument is being depreciated, split it into `samples` and `rewards` instead")
        samples, rewards = dataset

    if model_path:
        config.model.model_path = model_path

    trainer_cls = get_trainer(config.train.trainer)
    trainer = trainer_cls(
        config=config,
        reward_fn=reward_fn,
        metric_fn=metric_fn,
        stop_sequences=stop_sequences or [],
        **config.train.trainer_kwargs,
    )
    # the flight stream's `setup` row: the constructor ends here
    trainer.obs.end_init()

    batch_size = config.train.batch_size
    max_prompt_length = config.train.seq_length - config.method.gen_kwargs.get(
        "max_new_tokens", 0
    )
    if max_prompt_length <= 0:
        raise ValueError(
            f"train.seq_length ({config.train.seq_length}) must exceed "
            f"gen_kwargs['max_new_tokens'] "
            f"({config.method.gen_kwargs.get('max_new_tokens', 0)}): prompts "
            "would be truncated to zero tokens"
        )

    # --- online ----------------------------------------------------------
    if reward_fn:
        if prompts is None:
            raise ValueError("`prompts` are required for online training")
        if eval_prompts is None:
            eval_prompts = prompts[:batch_size]

        with trainer.obs.span("prompt_pipeline", prompts=len(prompts)):
            pipeline = get_pipeline(config.train.pipeline)(
                prompts, max_prompt_length, trainer.tokenizer
            )
            trainer.add_prompt_pipeline(pipeline)

    # --- offline RL ------------------------------------------------------
    elif rewards is not None:
        if samples is None:
            raise ValueError("`samples` are required alongside `rewards`")
        if eval_prompts is None:
            eval_prompts = [trainer.tokenizer.bos_token] * batch_size
        trainer.make_experience(samples, rewards, config.train.seq_length)

    # --- supervised / offline preference pairs ---------------------------
    else:
        if samples is None:
            raise ValueError("Either `samples`, `rewards` or `reward_fn` must be given")
        if eval_prompts is None:
            eval_prompts = [trainer.tokenizer.bos_token] * batch_size
        # SFT takes strings or (prompt, output) dialogues; DPO takes
        # (prompt, chosen, rejected) triples — the trainer validates
        trainer.make_experience(samples, None, config.train.seq_length)

    with trainer.obs.span("prompt_pipeline", prompts=len(eval_prompts)):
        eval_pipeline = get_pipeline(config.train.pipeline)(
            eval_prompts, max_prompt_length, trainer.tokenizer
        )
        trainer.add_eval_pipeline(eval_pipeline)

    import os

    resume = config.train.resume_from_checkpoint
    env_resume = os.environ.get("TRLX_TPU_RESUME_FROM")
    if env_resume:
        # the run supervisor's relaunch channel (scripts/supervise.py):
        # after a stalled exit (class 87) it points the next attempt at
        # the hang doctor's emergency snapshot — which auto-discovery
        # deliberately never picks up — without editing the config the
        # operator wrote
        logger.warning(
            "TRLX_TPU_RESUME_FROM=%s overrides "
            "train.resume_from_checkpoint=%r for this launch",
            env_resume, resume,
        )
        resume = env_resume
    if resume == "auto":
        from trlx_tpu.parallel import multihost as mh
        from trlx_tpu.utils.checkpointing import CheckpointCorruptError

        # discover the newest COMMITted checkpoint under checkpoint_dir;
        # torn directories (preemption mid-save) and deploy-only ones
        # (save_optimizer=false) are skipped, and "nothing yet" is a
        # fresh start — the standard relaunch loop on preemptible pods
        # points every attempt at the same command line. A checkpoint
        # that fails integrity verification is QUARANTINED by load()
        # (renamed *.corrupt) and discovery falls back to the previous
        # committed step instead of crashing every relaunch on poison.
        while True:
            resume = trainer.ckpt_manager.latest_resumable()
            if mh.is_multihost():
                # stale shared-filesystem metadata can show different
                # hosts different listings; every process must load the
                # SAME checkpoint (or none), so process 0's discovery wins
                resume = mh.allgather_object(resume)[0]
            if resume is None:
                logger.warning(
                    "resume_from_checkpoint='auto': no committed checkpoint "
                    "under %s — starting fresh", config.train.checkpoint_dir,
                )
                break
            logger.info("Resuming from checkpoint %s", resume)
            try:
                trainer.load(resume)
                break
            except CheckpointCorruptError as e:
                logger.error(
                    "auto-resume: %s — falling back to the previous "
                    "committed checkpoint", e,
                )
    elif resume:
        # an explicitly named checkpoint: a corrupt one is an error the
        # user must see (no silent fallback to a different step), and
        # the pinned path is NOT renamed — a transient storage mismatch
        # must not permanently break the path the user configured
        logger.info("Resuming from checkpoint %s", resume)
        trainer.load(resume, quarantine_corrupt=False)

    trainer.learn()
    return trainer
