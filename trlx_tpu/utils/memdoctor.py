"""Memory doctor: HBM admission control, OOM classification, and a
degrade-don't-die recovery ladder.

Every other failure class this framework survives — crashes (PR 1),
divergence (PR 3), corruption (PR 4), hangs (PR 5), dying workers
(PR 7/8) — produced a watchdog with a diagnosis and a recovery path.
``RESOURCE_EXHAUSTED`` had neither: a sizing mistake anywhere (page
pool, microbatch, activation footprint) killed the run with a raw XLA
allocation message, usually *after* a long compile had already burned
the allocation. This module gives HBM the same treatment wall-clock got
from the hang doctor:

  preflight admission control
      ``MemoryDoctor.preflight`` builds an analytic per-phase HBM plan
      (:func:`estimate_plan`: params / grads / optimizer state /
      activations for the fused block; page pools + draft model for the
      decode engine; transport/fleet buffers as host-side notes),
      checks the peak phase against the per-device budget
      (``memory_stats()['bytes_limit']`` where the backend reports one,
      or ``train.memory.hbm_bytes``), and FAILS an over-budget config
      with an itemized report *before* the first compile — a sizing
      mistake costs seconds, not the run. ``cross_check`` compares the
      plan against ``compiled.memory_analysis()`` on an AOT-lowered
      step where available (tests pin the goldens on CPU).
  runtime watermarks
      :class:`WatermarkSampler` — a host-side daemon thread reading
      ``device.memory_stats()`` on a fixed cadence, attributing the
      peak bytes to the phase in progress (the hang doctor's heartbeat
      registry already knows it). Crossing the high watermark for
      ``watermark_window`` consecutive samples raises the ``memory``
      guardrail signal (utils/guardrails.MEMORY_SIGNAL), which walks
      the PR 3 escalation ladder like any other health trip — HBM
      creep is a divergence of the memory curve. Per-phase peaks ride
      the trackers/bench as ``memory/peak_<phase>_mb``.
  OOM recovery ladder
      :func:`classify_oom` turns a RESOURCE_EXHAUSTED into an
      :class:`OOMEvent` (phase it struck, compile vs runtime, bytes it
      wanted); :meth:`MemoryDoctor.decide` picks the cheapest
      degradation that can relieve *that* phase:

        shrink_pool        rollout/prefill OOM: scale the decode
                           engine's page pool + slots down by
                           ``pool_shrink_factor`` (HEPPO-GAE's lesson:
                           rollout storage is the compressible half)
        split_microbatch   train OOM: double the gradient-accumulation
                           factor — same global batch, half the
                           activation residency; golden-checked equal
                           to the unsplit step (tests/test_memdoctor)
        remat              enable/escalate the activation-checkpoint
                           policy (ops/remat.py), trading recompute
                           FLOPs for residency
        rollback           restore the last health-gated checkpoint
                           (the PR 3 machinery) with the degraded
                           config PERSISTED in state.json, so a
                           supervise.py relaunch and ``trainer.load()``
                           resume already-degraded
        abort              itemized RuntimeError carrying the plan, the
                           event history and the degradation state —
                           the post-mortem a raw allocator message
                           never gives you

      Degradation is monotonic and persistent: ``degrade_state()`` is
      committed inside every atomic state.json, ``restore()`` merges by
      max (a rollback can never silently un-degrade), and a degraded
      checkpoint resumed under a config with the doctor disabled fails
      loudly instead of re-OOMing at the original sizes.

Everything here is host-side and jax-free at module scope; the clock,
sleep, and device-stats hooks are injectable so tier-1 tests run the
ladder on a fake allocator and a fake clock (tests/test_memdoctor.py).
"""

from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

# the ladder rungs, cheapest relief first; config may use an ordered
# subset (same contract as train.guardrails.ladder)
LADDER_ACTIONS = ("shrink_pool", "split_microbatch", "remat", "rollback", "abort")

# remat policies by increasing memory savings — the `remat` rung only
# escalates (never weakens a policy the user already set). Mirrors
# ops/remat.py's policy table.
REMAT_STRENGTH = (
    "none", "dots_saveable", "save_attn", "dots_with_no_batch_dims",
    "offload", "full", "save_nothing",
)


def remat_strength(policy) -> int:
    """Ordinal memory-savings rank of a remat policy (unknown/False -> 0)."""
    name = policy if isinstance(policy, str) else ("full" if policy else "none")
    try:
        return REMAT_STRENGTH.index(name)
    except ValueError:
        return 0


def is_degraded_record(d) -> bool:
    """Is a persisted ``memory_degrade`` record (state.json) actually
    degraded? The ONE definition — the trainer's resume gate,
    verify_ckpt's NOTE, and supervise.py's ledger all share it, so a
    future degradation dimension cannot silently disagree between
    checkers."""
    if not isinstance(d, dict):
        return False
    return bool(
        d.get("pool_shrinks")
        or int(d.get("accum_factor", 1) or 1) > 1
        or d.get("remat_policy")
    )


class MemoryAbortError(RuntimeError):
    """The memory doctor's itemized abort (ladder exhausted). Its
    message quotes the classified RESOURCE_EXHAUSTED, so it would
    string-match :func:`is_oom` — the explicit type check there keeps
    the OOM envelopes from re-classifying their own abort."""


class MemoryPlanError(RuntimeError):
    """Preflight admission control rejected the config: the analytic
    per-phase HBM plan exceeds the device budget. Carries the itemized
    report so the operator sees WHERE the bytes go before any compile."""

    def __init__(self, message: str, plan: "HBMPlan"):
        super().__init__(message)
        self.plan = plan


@dataclass
class MemoryConfig:
    """Parsed ``train.memory`` section (plain dict in YAML).

    enabled             master switch (default off: behavior-preserving
                        — no preflight, no sampler, OOMs propagate raw).
    preflight           "off" | "warn" | "enforce": what an over-budget
                        plan does before the first compile ("enforce"
                        raises :class:`MemoryPlanError` with the
                        itemized report; "warn" logs it).
    hbm_bytes           per-device HBM budget; 0 = discover from
                        ``memory_stats()['bytes_limit']`` (backends
                        without stats — CPU — leave the budget unknown
                        and preflight degrades to report-only).
    headroom            fraction of the budget a plan may fill (the
                        rest absorbs fragmentation + runtime temps the
                        analytic plan cannot see).
    high_watermark      runtime bytes-in-use fraction that raises the
                        ``memory`` guardrail signal.
    watermark_window    consecutive high samples before the trip
                        (debounce: one transient peak is not creep).
    sample_interval_s   watermark sampler cadence.
    ladder              ordered subset of
                        ``("shrink_pool","split_microbatch","remat",
                        "rollback","abort")`` the OOM doctor may walk.
    pool_shrink_factor  page-pool/slots multiplier per shrink_pool rung.
    max_pool_shrinks    shrink_pool budget before the ladder moves on.
    max_splits          split_microbatch budget (each rung doubles the
                        accumulation factor).
    remat_escalation    the policy the remat rung switches to (only if
                        strictly stronger than the configured one).
    accept_undegrade    resume a DEGRADED checkpoint without adopting
                        its degradation (you are asserting the original
                        sizes fit now — e.g. after moving to bigger
                        chips). Default False: fails loudly instead of
                        re-OOMing at the sizes that already OOMed.
    """

    enabled: bool = False
    preflight: str = "enforce"
    hbm_bytes: int = 0
    headroom: float = 0.9
    high_watermark: float = 0.92
    watermark_window: int = 3
    sample_interval_s: float = 0.5
    ladder: Tuple[str, ...] = LADDER_ACTIONS
    pool_shrink_factor: float = 0.5
    max_pool_shrinks: int = 2
    max_splits: int = 3
    remat_escalation: str = "dots_with_no_batch_dims"
    accept_undegrade: bool = False

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "MemoryConfig":
        d = dict(d or {})
        known = set(cls.__dataclass_fields__)
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"train.memory: unknown keys {sorted(unknown)} "
                f"(known: {sorted(known)})"
            )
        if "ladder" in d:
            ladder = tuple(d["ladder"])
            bad = [a for a in ladder if a not in LADDER_ACTIONS]
            if bad:
                raise ValueError(
                    f"train.memory.ladder: unknown actions {bad} "
                    f"(choose from {list(LADDER_ACTIONS)})"
                )
            order = [LADDER_ACTIONS.index(a) for a in ladder]
            if order != sorted(order) or len(set(ladder)) != len(ladder):
                raise ValueError(
                    "train.memory.ladder must be an ordered subset of "
                    f"{list(LADDER_ACTIONS)}, got {list(ladder)}"
                )
            d["ladder"] = ladder
        cfg = cls(**d)
        if cfg.preflight not in ("off", "warn", "enforce"):
            raise ValueError(
                f"train.memory.preflight must be off/warn/enforce, got "
                f"{cfg.preflight!r}"
            )
        if not 0.0 < cfg.pool_shrink_factor < 1.0:
            raise ValueError(
                "train.memory.pool_shrink_factor must be in (0, 1), got "
                f"{cfg.pool_shrink_factor}"
            )
        if cfg.remat_escalation not in REMAT_STRENGTH:
            raise ValueError(
                f"train.memory.remat_escalation={cfg.remat_escalation!r} "
                f"not in {list(REMAT_STRENGTH)}"
            )
        return cfg


# ---------------------------------------------------------------------------
# OOM classification
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "OOM when allocating")

# "Attempting to allocate 8.25GiB" / "allocating 1234567 bytes" /
# "trying to allocate 8589934592 bytes"
_BYTES_RE = re.compile(
    r"(?:allocat\w*)\s+(?:of\s+)?([\d.]+)\s*(GiB|MiB|KiB|G|M|K|bytes|B)\b",
    re.IGNORECASE,
)
_UNIT = {
    "gib": 1 << 30, "g": 1 << 30, "mib": 1 << 20, "m": 1 << 20,
    "kib": 1 << 10, "k": 1 << 10, "bytes": 1, "b": 1,
}

_COMPILE_MARKERS = (
    "while compiling", "during compilation", "buffer assignment",
    "constant allocation", "compile time", "while lowering",
)


def is_oom(exc: BaseException) -> bool:
    """Is this exception an accelerator allocation failure? Matched on
    the message (jaxlib's XlaRuntimeError carries RESOURCE_EXHAUSTED
    verbatim) rather than the type, so the chaos harness's simulated
    OOMs and future jaxlib renames both classify. The doctor's own
    :class:`MemoryAbortError` quotes the allocator text it classified
    — excluded by type, or an outer envelope would re-handle it."""
    if isinstance(exc, MemoryAbortError):
        return False
    text = f"{type(exc).__name__}: {exc}"
    return any(m in text for m in _OOM_MARKERS)


@dataclass
class OOMEvent:
    """One classified RESOURCE_EXHAUSTED: which phase dispatched the
    work that blew the allocator, whether it struck at compile time
    (buffers not yet donated: a retry after degradation is safe) or at
    runtime, and how many bytes the failed allocation wanted."""

    phase: str  # rollout_prefill | rollout_decode | fused_block | train_step | ...
    stage: str  # "compile" | "runtime"
    bytes_requested: int
    detail: str

    def summary(self) -> str:
        want = (
            f"{self.bytes_requested / (1 << 30):.2f} GiB"
            if self.bytes_requested else "unknown bytes"
        )
        return (
            f"RESOURCE_EXHAUSTED in phase {self.phase!r} "
            f"({self.stage}, wanted {want})"
        )


def classify_oom(exc: BaseException, phase: str) -> OOMEvent:
    """Exception + the phase that dispatched it -> :class:`OOMEvent`.
    The phase comes from the call site (the trainer knows what it
    dispatched); compile-vs-runtime and the requested byte count are
    parsed from the allocator message."""
    text = str(exc)
    m = _BYTES_RE.search(text)
    nbytes = 0
    if m:
        nbytes = int(float(m.group(1)) * _UNIT[m.group(2).lower()])
    stage = (
        "compile"
        if any(k in text.lower() for k in _COMPILE_MARKERS)
        else "runtime"
    )
    return OOMEvent(
        phase=phase, stage=stage, bytes_requested=nbytes,
        detail=text.splitlines()[0][:400] if text else type(exc).__name__,
    )


# ---------------------------------------------------------------------------
# the HBM plan (preflight admission control)
# ---------------------------------------------------------------------------

@dataclass
class PlanItem:
    phase: str  # "steady" | "train" | "rollout" | "host"
    component: str
    bytes: int
    note: str = ""


@dataclass
class HBMPlan:
    """Itemized per-phase HBM accounting. ``steady`` items (params,
    optimizer state, reference) are resident in every phase; ``train``
    and ``rollout`` items are phase-local, so the admission check is
    ``steady + max(train, rollout)`` against ``headroom * budget``.
    ``host`` items (transport/fleet buffers) are informational — they
    live in host RAM, not HBM."""

    items: List[PlanItem] = field(default_factory=list)
    budget_bytes: int = 0
    headroom: float = 0.9

    def add(self, phase: str, component: str, nbytes: int, note: str = "") -> None:
        self.items.append(PlanItem(phase, component, int(nbytes), note))

    def total(self, phase: str) -> int:
        return sum(i.bytes for i in self.items if i.phase == phase)

    def phase_totals(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for i in self.items:
            out[i.phase] = out.get(i.phase, 0) + i.bytes
        return out

    def peak_phase(self) -> Tuple[str, int]:
        """(phase, device bytes) of the worst phase: steady-state
        residency plus that phase's own items."""
        steady = self.total("steady")
        peaks = {
            p: steady + t
            for p, t in self.phase_totals().items()
            if p not in ("steady", "host")
        } or {"steady": steady}
        worst = max(peaks, key=peaks.get)
        return worst, peaks[worst]

    def over_budget(self) -> bool:
        if self.budget_bytes <= 0:
            return False  # unknown budget: nothing to enforce against
        _, peak = self.peak_phase()
        return peak > self.headroom * self.budget_bytes

    def report(self) -> str:
        """The itemized per-phase table an over-budget rejection (or a
        curious operator) reads."""
        lines = ["HBM plan (per device):"]
        for phase in ("steady", "train", "rollout", "host"):
            items = [i for i in self.items if i.phase == phase]
            if not items:
                continue
            total = sum(i.bytes for i in items)
            unit = "host RAM" if phase == "host" else "HBM"
            lines.append(f"  [{phase}] total {_fmt(total)} ({unit})")
            for i in sorted(items, key=lambda x: -x.bytes):
                note = f"  — {i.note}" if i.note else ""
                lines.append(f"    {i.component:<28} {_fmt(i.bytes):>10}{note}")
        worst, peak = self.peak_phase()
        lines.append(f"  peak phase: {worst!r} at {_fmt(peak)} device-resident")
        if self.budget_bytes > 0:
            frac = peak / self.budget_bytes
            lines.append(
                f"  budget: {_fmt(self.budget_bytes)} x headroom "
                f"{self.headroom:.0%} -> {_fmt(int(self.headroom * self.budget_bytes))} "
                f"admitted; plan fills {frac:.0%} of the device"
            )
        else:
            lines.append(
                "  budget: unknown (backend reports no memory_stats and "
                "train.memory.hbm_bytes is 0) — report only, nothing enforced"
            )
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        worst, peak = self.peak_phase()
        return {
            "items": [
                {"phase": i.phase, "component": i.component,
                 "bytes": i.bytes, "note": i.note}
                for i in self.items
            ],
            "phase_totals": self.phase_totals(),
            "peak_phase": worst,
            "peak_bytes": peak,
            "budget_bytes": self.budget_bytes,
            "headroom": self.headroom,
            "over_budget": self.over_budget(),
        }


def _fmt(nbytes: int) -> str:
    if abs(nbytes) >= 1 << 30:
        return f"{nbytes / (1 << 30):.2f}GiB"
    if abs(nbytes) >= 1 << 20:
        return f"{nbytes / (1 << 20):.2f}MiB"
    return f"{nbytes / 1024:.1f}KiB"


def tree_bytes(tree) -> int:
    """Total bytes of every array-like leaf (arrays, ShapeDtypeStructs
    — anything with .shape/.dtype)."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    return total


def device_hbm_bytes() -> int:
    """Per-device HBM from the backend (0 when the backend reports no
    stats — CPU; callers fall back to ``train.memory.hbm_bytes``)."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return 0
    if not stats:
        return 0
    return int(stats.get("bytes_limit", 0) or 0)


def device_bytes_in_use() -> Optional[int]:
    """Live bytes-in-use (None when the backend reports no stats)."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    used = stats.get("bytes_in_use")
    return int(used) if used is not None else None


# activation residency coefficients by remat policy: saved residuals
# per layer, in units of [rows, seq, hidden] activations. Analytic
# estimates (the preflight is admission control, not a profiler);
# cross-checked loosely against memory_analysis() in tests.
_ACT_COEFF = {
    "none": 14.0,            # qkv + attn out + 4x mlp up/act + norms
    "dots_saveable": 6.0,    # matmul outputs only
    "save_attn": 3.0,        # layer boundaries + attention residuals
    "dots_with_no_batch_dims": 2.0,  # weight-stationary results only
    "offload": 2.0,          # same saves, but resident in host memory
    "full": 2.0,             # layer boundaries only
    "save_nothing": 2.0,
}


def _act_coeff(remat_policy) -> float:
    name = (
        remat_policy if isinstance(remat_policy, str)
        else ("full" if remat_policy else "none")
    )
    return _ACT_COEFF.get(name, 14.0)


def activation_bytes(rows_dev, seq, hidden, layers, remat_policy, csize) -> int:
    """Train-phase activation residency estimate — the ONE formula
    behind both the live preflight (estimate_plan) and the offline CLI
    (analytic_plan), so the two admission verdicts cannot drift."""
    return int(rows_dev * seq * hidden * layers * _act_coeff(remat_policy) * csize)


def logits_bytes(rows_dev, seq, vocab, chunks) -> int:
    """fp32 logits materialization (full, or per train.logit_chunks)."""
    chunks = max(int(chunks or 0), 0)
    rows = seq if chunks == 0 else -(-seq // chunks)
    return int(rows_dev * rows * vocab * 4)


def epoch_batch_bytes(n_rows, seq, ways) -> int:
    """Device-resident rollout store for the fused inner loop (~8
    int32-sized fields per token)."""
    return int(n_rows * seq * 4 * 8 // max(ways, 1))


def _dtype_size(name: Optional[str]) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}.get(name or "float32", 4)


def engine_pool_bytes(spec, model_cfg, prompt_len: int, max_new: int) -> int:
    """Device bytes of the decode-engine POLICY page pool(s) for a
    resolved :class:`~trlx_tpu.models.gen_engine.EngineSpec` (mirrors
    paged_kv.init_pool's shapes, x data_groups lane-group pools).
    Speculative decoding adds :func:`draft_pool_bytes` on top — a full
    second pool for a full-copy draft, or just the branch layers when
    the hydra trunk is shared."""
    from trlx_tpu.ops import paged_kv

    K = spec.draft_k if spec.spec_decode else 0
    MP = paged_kv.pages_per_slot(prompt_len, max_new + K, spec.page_size)
    groups = max(getattr(spec, "data_groups", 1), 1)
    # an explicit pool_pages is the TOTAL budget split ceil(1/G) per
    # lane group (engine_generate_grouped); worst-case sizing is per
    # group — both match the engine's actual allocation
    explicit = -(-spec.pool_pages // groups) if spec.pool_pages else 0
    NP = (explicit or (1 + spec.slots * MP)) if spec.paged else (
        1 + spec.slots * MP
    )
    L = model_cfg.n_layer
    cells = L * NP * spec.page_size * model_cfg.n_kv_head * model_cfg.head_dim
    if spec.kv_quant == "int8":
        # int8 pk + pv, plus fp32 per-row scales (one per (page, pos, head))
        per_pool = 2 * cells + 2 * (cells // model_cfg.head_dim) * 4
    else:
        itemsize = 2 if str(model_cfg.dtype) in ("bfloat16", "bf16") else 4
        per_pool = 2 * cells * itemsize
    # sharded lane groups: G per-group pools (with the group axis
    # sharded over the mesh the per-device share is 1/G of this, but
    # the preflight plans the unsharded ceiling)
    return per_pool * groups


def draft_pool_bytes(pool_b: int, n_layer: int, shared_layers: int) -> int:
    """Bytes the speculative DRAFT adds on top of the policy pool: a
    full-copy draft keeps its own full-depth pool (``pool_b``); a hydra
    draft with ``shared_layers`` trunk layers shared stores only its
    BRANCH layers (gen_engine's extended-pool layout — trunk KV is held
    once), i.e. (L - shared)/L of one pool."""
    if shared_layers <= 0:
        return pool_b
    return pool_b * (n_layer - shared_layers) // n_layer


def estimate_plan(trainer) -> HBMPlan:
    """Analytic per-phase HBM plan from a LIVE trainer (exact tree
    bytes for state; analytic formulas for activations and pools).
    Phases:

      steady   params + optimizer state + frozen reference (+LoRA etc.)
      train    gradients + fused epoch batch + activation residency of
               one microbatch + the logits materialization
      rollout  decode-time param copy + decode engine page pools +
               draft model (speculative)
      host     experience-transport / fleet buffers (host RAM, FYI)
    """
    cfg = trainer.config
    train = cfg.train
    mcfg = trainer.memdoctor.cfg if getattr(trainer, "memdoctor", None) else (
        MemoryConfig()
    )
    plan = HBMPlan(
        budget_bytes=mcfg.hbm_bytes or device_hbm_bytes(),
        headroom=mcfg.headroom,
    )

    ways = trainer.data_ways()  # batch rows shard over dp*fsdp
    # state trees shard over fsdp ONLY (dp replicates them)
    shard = max(trainer.mesh.shape.get("fsdp", 1), 1)
    shard_note = (
        f"sharded over fsdp={shard}" if shard > 1 else "replicated per device"
    )
    params_b = tree_bytes(trainer.params)
    mc0 = _model_cfg(trainer)
    if getattr(mc0, "routed", False):
        shard_note += (f"; {mc0.n_experts_held} of {mc0.n_routed_experts} routed experts a "
                       "layer are held here, the shared expert whole")
    plan.add("steady", "params", params_b // shard, shard_note)
    opt_b = tree_bytes(trainer.opt_state)
    plan.add("steady", "opt_state", opt_b // shard, shard_note)
    ref = getattr(trainer, "ref_params", None)
    if ref is not None:
        plan.add("steady", "ref_params", tree_bytes(ref) // shard,
                 "frozen reference (hydra branch or full copy)")

    # ---- train phase -------------------------------------------------
    float_params = tree_bytes(list(_float_leaves(trainer.params)))
    gsize = _dtype_size(train.grads_dtype) if train.grads_dtype else _dtype_size(
        train.param_dtype
    )
    grads_b = float_params * gsize // _dtype_size(train.param_dtype)
    plan.add("train", "grads", grads_b // shard,
             f"dtype {train.grads_dtype or train.param_dtype}"
             + ("; fp32 accumulator rides per-microbatch" if trainer.num_mb > 1 else ""))

    rows_dev = max(trainer.mb_size // max(ways, 1), 1)
    S = train.seq_length
    E = _hidden(trainer)
    L = _layers(trainer)
    act_size = _dtype_size(train.compute_dtype)
    streams = int(getattr(_model_cfg(trainer), "residual_streams", 1))
    plan.add(
        "train", "activations",
        activation_bytes(rows_dev, S, E * streams, L, train.remat_policy, act_size),
        f"{trainer.num_mb}x accumulation, mb_size {trainer.mb_size}, "
        f"remat {train.remat_policy!r} (coeff {_act_coeff(train.remat_policy):g})"
        + (f", {streams} residual streams" if streams > 1 else ""),
    )
    V = _vocab(trainer)
    chunks = max(int(train.logit_chunks or 0), 0)
    plan.add(
        "train", "logits", logits_bytes(rows_dev, S, V, chunks),
        "full materialization — set train.logit_chunks"
        if chunks == 0 else f"chunked x{chunks}",
    )
    # the fused path keeps the WHOLE epoch batch device-resident
    n_rows = int(getattr(cfg.method, "num_rollouts", train.batch_size))
    plan.add(
        "train", "epoch_batch", epoch_batch_bytes(n_rows, S, ways),
        "device-resident rollout store (fused_inner_loop)",
    )

    # ---- rollout phase -----------------------------------------------
    import numpy as np

    try:
        decode_size = int(np.dtype(_model_cfg(trainer).dtype).itemsize)
    except Exception:
        decode_size = 2
    plan.add(
        "rollout", "decode_params",
        params_b * decode_size // _dtype_size(train.param_dtype),
        "cast_params_for_decode copy",
    )
    # the engine/static cache rows are estimates over model-family-
    # specific config fields: a family this formula doesn't know must
    # degrade to an honest "unestimated" row, never crash a preflight
    try:
        engine_cfg = getattr(trainer, "_engine_cfg", None)
        chunk = int(getattr(cfg.method, "chunk_size", train.batch_size))
        if engine_cfg is not None and engine_cfg.enabled:
            max_new = trainer.generate_experience_settings.max_new_tokens
            prompt_len = max(S - max_new, 1)
            spec = trainer._engine_spec(chunk)
            pool_b = engine_pool_bytes(
                spec, _model_cfg(trainer), prompt_len, max_new
            )
            plan.add(
                "rollout", "engine_kv_pool", pool_b,
                f"{spec.slots} slots, page_size {spec.page_size}, "
                f"quant {spec.kv_quant or 'none'}"
                + (f", pool scaled x{trainer.memdoctor.pool_scale():g}"
                   if getattr(trainer, "memdoctor", None)
                   and trainer.memdoctor.pool_scale() < 1.0 else ""),
            )
            if spec.spec_decode:
                sh = getattr(spec, "draft_shared_layers", 0)
                db = draft_pool_bytes(
                    pool_b, _model_cfg(trainer).n_layer, sh
                )
                plan.add(
                    "rollout", "engine_draft_pool", db,
                    f"draft branch layers only ({sh} trunk layers share "
                    "the policy pool)" if sh
                    else "speculative draft keeps its own pool (full copy)",
                )
                if ref is not None:
                    plan.add("rollout", "draft_params", tree_bytes(ref),
                             "reference as draft (hydra composes a trunk copy)")
        else:
            # static sampler: contiguous whole-batch KV cache
            mc = _model_cfg(trainer)
            kv_quant = getattr(mc, "kv_cache_quant", None)
            kv_size = 1 if kv_quant == "int8" else decode_size
            # numbers a cached position costs a row in one layer: 2 x heads
            # x head size, or a latent cache's rank + rotary channels
            per_position = getattr(mc, "cache_elems_per_position", None) or (
                2 * getattr(mc, "n_kv_head", _heads(trainer))
                * getattr(mc, "head_dim", E // max(_heads(trainer), 1))
            )
            caching = getattr(mc, "cache_layers", L)
            kv_b = int(caching * chunk * S * per_position * kv_size)
            plan.add("rollout", "static_kv_cache", kv_b,
                     f"whole-chunk cache, {per_position} numbers a position a layer"
                     + (" (latent)" if getattr(mc, "latent", False) else "")
                     + (f", the {caching} of {L} layers that cache" if caching < L else "")
                     + f", quant {kv_quant or 'none'}")
            state_b = recurrent_state_bytes(mc, chunk, decode_size)
            if state_b:
                plan.add("rollout", "recurrent_state", state_b, _recurrent_note(mc))
    except Exception as exc:
        plan.add("rollout", "kv_cache", 0,
                 f"unestimated for this model family ({type(exc).__name__})")

    # ---- host-side buffers (FYI rows, not HBM) -----------------------
    exp_cfg = getattr(trainer, "_exp_cfg", None)
    if exp_cfg is not None and exp_cfg.enabled:
        depth = int(getattr(exp_cfg, "max_depth", 4) or 4)
        chunk = int(getattr(cfg.method, "chunk_size", train.batch_size))
        plan.add("host", "exp_queue", epoch_batch_bytes(depth * chunk, S, 1),
                 f"experience transport, max_depth {depth}")
    fleet_cfg = getattr(trainer, "_fleet_cfg", None)
    if fleet_cfg is not None and getattr(fleet_cfg, "enabled", False):
        plan.add("host", "fleet_broadcast", params_b,
                 "one host param copy per weight publish")

    for item in trainer._extra_plan_items():
        plan.items.append(item)
    return plan


def _float_leaves(tree):
    import jax
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(tree):
        dt = getattr(leaf, "dtype", None)
        if dt is not None and np.issubdtype(np.dtype(dt), np.floating):
            yield leaf


def _model_cfg(trainer):
    return trainer._lm().cfg


def _hidden(trainer) -> int:
    return int(getattr(_model_cfg(trainer), "hidden_size", 768))


def _layers(trainer) -> int:
    return int(getattr(_model_cfg(trainer), "n_layer", 12))


def _heads(trainer) -> int:
    return int(getattr(_model_cfg(trainer), "n_head", 12))


def _vocab(trainer) -> int:
    return int(getattr(_model_cfg(trainer), "vocab_size", 50257))


def cross_check(plan: HBMPlan, compiled) -> Optional[Dict[str, int]]:
    """Compare the plan against an AOT-compiled executable's
    ``memory_analysis()`` (None when the backend doesn't implement it).
    Returns the analysis numbers for the caller to log/assert — the
    plan's state items should account for the argument bytes, and the
    temp bytes bound the activation estimate from below."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    try:
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    except AttributeError:
        return None


# ---------------------------------------------------------------------------
# runtime watermarks
# ---------------------------------------------------------------------------

class WatermarkSampler:
    """Host-side HBM sampler: a daemon thread reads the device's
    bytes-in-use on a fixed cadence, attributes the reading to the
    phase in progress, and latches a trip when the high watermark is
    crossed for ``watermark_window`` consecutive samples. The trainer
    consumes the trip at its next safe point (``consume_trip``) and
    forwards it as the ``memory`` guardrail signal.

    ``stats_fn`` returns (bytes_in_use, bytes_limit) or None; the
    default reads ``jax.local_devices()[0].memory_stats()`` and
    silently no-ops on backends without stats (CPU). ``phase_fn``
    names the current phase (the trainer wires the hang doctor's
    heartbeat registry in). Both injectable, so tests run the sampler
    inline on a fake allocator with no thread."""

    def __init__(
        self,
        config: MemoryConfig,
        stats_fn: Optional[Callable[[], Optional[Tuple[int, int]]]] = None,
        phase_fn: Optional[Callable[[], str]] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.cfg = config
        self._stats_fn = stats_fn or self._default_stats
        self._phase_fn = phase_fn or (lambda: "run")
        self._clock = clock
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self.peaks: Dict[str, int] = {}  # phase -> peak bytes_in_use
        self.samples = 0
        self._high_streak = 0
        self._trip_detail: Optional[str] = None
        # total CONSUMED watermark trips (distinct from the guardrail
        # trip history, which also records OOM-event `memory` trips)
        self.watermark_trips = 0
        self._warned_no_stats = False
        # chaos `hbm_creep`: the next `creep` samples read as 100% full
        # (the deterministic stand-in for a real leak's slow climb)
        self._creep_samples = 0

    @staticmethod
    def _default_stats() -> Optional[Tuple[int, int]]:
        used = device_bytes_in_use()
        if used is None:
            return None
        return used, device_hbm_bytes()

    def set_phase_fn(self, phase_fn: Callable[[], Optional[str]]) -> None:
        """Late-bind the phase attribution source (the trainer wires
        the hang doctor's registry in after construction)."""
        self._phase_fn = lambda: phase_fn() or "run"

    def inject_creep(self, samples: Optional[int] = None) -> None:
        """Chaos ``hbm_creep`` body: make the next ``samples`` readings
        saturate the watermark, as a silently leaking allocation would."""
        with self._lock:
            self._creep_samples += samples or self.cfg.watermark_window

    def sample(self) -> None:
        """One sampling step (the thread calls this on cadence; tests
        call it directly)."""
        stats = self._stats_fn()
        phase = self._phase_fn() or "run"
        with self._lock:
            creep = self._creep_samples > 0
            if creep:
                self._creep_samples -= 1
        if stats is None and not creep:
            if not self._warned_no_stats and self.samples == 0:
                self._warned_no_stats = True
                logger.info(
                    "memory doctor: backend reports no memory_stats — "
                    "runtime watermarks are inactive (preflight and the "
                    "OOM ladder still apply)"
                )
            return
        if creep:
            limit = (stats[1] if stats else 0) or self.cfg.hbm_bytes or (1 << 30)
            used = limit  # saturated
        else:
            used, limit = stats
            limit = limit or self.cfg.hbm_bytes
        with self._lock:
            self.samples += 1
            if not creep and used > self.peaks.get(phase, 0):
                # creep-forced readings are fabricated — they must
                # drive the trip, never the real peak telemetry
                self.peaks[phase] = int(used)
            if limit and used >= self.cfg.high_watermark * limit:
                self._high_streak += 1
                if (
                    self._high_streak >= self.cfg.watermark_window
                    and self._trip_detail is None
                ):
                    self._trip_detail = (
                        f"HBM bytes-in-use {_fmt(int(used))} crossed the "
                        f"{self.cfg.high_watermark:.0%} watermark of "
                        f"{_fmt(int(limit))} for {self._high_streak} "
                        f"consecutive samples (phase {phase!r})"
                    )
            elif self._creep_samples == 0:
                # a real below-watermark reading resets the streak —
                # but not while an injected creep burst is still
                # pending, or a daemon-thread sample interleaving the
                # inline injection could break the "deterministic trip"
                # contract on stats-reporting backends
                self._high_streak = 0

    def consume_trip(self) -> Optional[str]:
        """The latched watermark trip, if any (one-shot: consuming
        re-arms the sampler)."""
        with self._lock:
            detail, self._trip_detail = self._trip_detail, None
            if detail is not None:
                self._high_streak = 0
                self.watermark_trips += 1
            return detail

    def peak_stats(self) -> Dict[str, float]:
        """``memory/peak_<phase>_mb`` scalars for trackers/bench."""
        with self._lock:
            return {
                f"memory/peak_{phase}_mb": round(b / (1 << 20), 2)
                for phase, b in self.peaks.items()
            }

    # -- thread lifecycle ------------------------------------------------

    def start(self) -> None:
        if not self.cfg.enabled or self._thread is not None:
            return
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._run, name="hbm-watermark", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop_evt.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)

    def _run(self) -> None:
        while not self._stop_evt.wait(self.cfg.sample_interval_s):
            try:
                self.sample()
            except Exception:
                logger.exception("memory doctor: watermark sample failed")


# ---------------------------------------------------------------------------
# the recovery ladder
# ---------------------------------------------------------------------------

class MemoryDoctor:
    """The degrade-don't-die state machine. Holds the monotonic
    degradation state (pool shrinks, gradient-accumulation factor,
    remat escalation), decides the next ladder action for a classified
    OOM, and serializes itself into state.json so a relaunch resumes
    already-degraded. Host-side bookkeeping only — trainer/base.py owns
    executing the actions (the same split as utils/guardrails.py)."""

    def __init__(self, config: MemoryConfig):
        self.cfg = config
        self.pool_shrinks = 0
        self.accum_factor = 1  # multiplier on the configured num_mb
        self.remat_policy: Optional[str] = None  # None = untouched
        self.rollbacks = 0
        self.events: List[Dict[str, Any]] = []  # classified OOMs + actions
        self.sampler = WatermarkSampler(config)

    @property
    def enabled(self) -> bool:
        return self.cfg.enabled

    @property
    def degraded(self) -> bool:
        return is_degraded_record(self.degrade_state())

    def pool_scale(self) -> float:
        return self.cfg.pool_shrink_factor ** self.pool_shrinks

    # -- decisions -------------------------------------------------------

    def decide(self, event: OOMEvent, caps: Dict[str, bool]) -> str:
        """The cheapest ladder action that can relieve ``event``'s
        phase, given what the run can actually do (``caps``: the
        trainer's capability flags — e.g. ``shrink_pool`` is only
        meaningful with the decode engine on, ``split_microbatch``
        needs a divisible microbatch). Rung budgets are enforced here;
        an exhausted, incapable, or phase-irrelevant rung is skipped
        (splitting the train microbatch cannot relieve a rollout
        prefill OOM, and shrinking the rollout pool cannot relieve a
        fused-block OOM). Falls through to ``abort``."""
        if event.phase.startswith("rollout"):
            # decode-side allocations: only the engine pool is elastic
            relevant = ("shrink_pool", "abort")
        elif event.phase == "experience":
            # the teacher-forced scoring forward is forward-only: no
            # rung shrinks it at runtime (train.logit_chunks is the
            # config-time fix) — the ladder's value here is the
            # classified, itemized abort instead of a raw allocator
            # error, and the report's last line says what to re-size
            relevant = ("abort",)
        else:
            # train-side (fused_block / train_step / experience):
            # activation+gradient residency is what degrades
            relevant = ("split_microbatch", "remat", "rollback", "abort")
        for action in self.cfg.ladder:
            if action not in relevant:
                continue
            if action == "shrink_pool":
                if caps.get("shrink_pool") and self.pool_shrinks < self.cfg.max_pool_shrinks:
                    return action
            elif action == "split_microbatch":
                if caps.get("split_microbatch") and self._splits < self.cfg.max_splits:
                    return action
            elif action == "remat":
                if caps.get("remat") and self.remat_policy is None:
                    return action
            elif action == "rollback":
                if caps.get("rollback"):
                    return action
            else:  # abort
                return "abort"
        return "abort"

    @property
    def _splits(self) -> int:
        return max(self.accum_factor.bit_length() - 1, 0)

    def note(self, event: OOMEvent, action: str) -> None:
        """Record the classified OOM and the action taken (the history
        rides the itemized abort and state.json)."""
        self.events.append({
            "phase": event.phase,
            "stage": event.stage,
            "bytes_requested": event.bytes_requested,
            "action": action,
        })
        if action == "shrink_pool":
            self.pool_shrinks += 1
        elif action == "split_microbatch":
            self.accum_factor *= 2
        elif action == "rollback":
            self.rollbacks += 1
        logger.warning(
            "memory doctor: %s -> %s (degradation now: %s)",
            event.summary(), action, self.describe(),
        )

    def note_remat(self, policy: str) -> None:
        self.remat_policy = policy

    def describe(self) -> str:
        if not self.degraded:
            return "none"
        parts = []
        if self.pool_shrinks:
            parts.append(
                f"pool x{self.pool_scale():g} ({self.pool_shrinks} shrinks)"
            )
        if self.accum_factor > 1:
            parts.append(f"grad-accum x{self.accum_factor}")
        if self.remat_policy is not None:
            parts.append(f"remat={self.remat_policy}")
        return ", ".join(parts)

    def abort_report(self, event: OOMEvent, plan: Optional[HBMPlan]) -> str:
        """The itemized abort message: what failed, what was already
        tried, where the plan says the bytes go."""
        lines = [
            f"memory doctor: ladder exhausted — {event.summary()}",
            f"  degradation applied: {self.describe()}",
            f"  OOM history ({len(self.events)} events): " + "; ".join(
                f"{e['phase']}/{e['stage']}->{e['action']}"
                for e in self.events[-8:]
            ),
        ]
        if plan is not None:
            lines.append(plan.report())
        lines.append(
            "  next: lower method.chunk_size / train.batch_size, raise "
            "mesh fsdp, or move to a larger device — then resume from "
            "the last committed checkpoint"
        )
        return "\n".join(lines)

    # -- persistence -----------------------------------------------------

    def degrade_state(self) -> Dict[str, Any]:
        """The state.json payload (``memory_degrade``): enough for a
        relaunch — supervise.py or a bare trainer.load() — to resume
        already-degraded instead of re-OOMing at the original sizes."""
        return {
            "pool_shrinks": self.pool_shrinks,
            "accum_factor": self.accum_factor,
            "remat_policy": self.remat_policy,
            "rollbacks": self.rollbacks,
            "events": self.events[-16:],
        }

    def restore(self, state: Optional[Dict[str, Any]]) -> None:
        """Adopt a persisted degradation level, merging by MAX per
        field: a guardrail rollback restores an older state.json, and
        the degradation the live run just escalated to must survive it
        (monotonic — the OOM that forced it is still real)."""
        if not state:
            return
        self.pool_shrinks = max(self.pool_shrinks, int(state.get("pool_shrinks", 0)))
        self.accum_factor = max(self.accum_factor, int(state.get("accum_factor", 1)))
        saved = state.get("remat_policy")
        if saved is not None and (
            self.remat_policy is None
            or remat_strength(saved) > remat_strength(self.remat_policy)
        ):
            self.remat_policy = saved
        self.rollbacks = max(self.rollbacks, int(state.get("rollbacks", 0)))
        if state.get("events"):
            saved_ev = list(state["events"])
            # in-process rollback: the live list already CONTAINS the
            # checkpoint's events (they happened in this process) —
            # prepending would double-count them on every rollback
            if self.events[: len(saved_ev)] != saved_ev:
                self.events = saved_ev + self.events


def build_memdoctor(train_config) -> MemoryDoctor:
    """TrainConfig -> doctor (the ``memory`` field is a plain dict so
    the flat config dataclass stays YAML/back-compatible)."""
    return MemoryDoctor(
        MemoryConfig.from_dict(getattr(train_config, "memory", None))
    )


# ---------------------------------------------------------------------------
# config-only analytic plan (scripts/hbm_plan.py — no allocation)
# ---------------------------------------------------------------------------

def analytic_param_count(tcfg: Dict[str, Any]) -> int:
    """Parameter count from transformer-config numbers alone (embedding
    + per-layer attention/MLP/norms + final norm): the zero-allocation
    path the preflight CLI uses so a 20B plan never touches a device.
    ~1% accuracy against real GPT-2-family trees — admission control,
    not an audit."""
    V = int(tcfg.get("vocab_size", 50257))
    E = int(tcfg.get("hidden_size", 768))
    L = int(tcfg.get("n_layer", 12))
    P = int(tcfg.get("n_positions", 1024))
    H = int(tcfg.get("n_head", 12))
    Hkv = int(tcfg.get("n_kv_head", H))
    D = int(tcfg.get("head_dim", E // max(H, 1)))
    I = int(tcfg.get("intermediate_size", 4 * E))
    attn = E * (H * D) + E * (2 * Hkv * D) + (H * D) * E + (H * D + 2 * Hkv * D + E)
    mlp = E * I + I * E + I + E
    norms = 4 * E
    if not (tcfg.get("kv_lora_rank") or tcfg.get("n_routed_experts")
            or int(tcfg.get("residual_streams", 1)) > 1 or _mixers(tcfg)):
        return V * E + P * E + L * (attn + mlp + norms) + 2 * E
    return _family_param_count(tcfg)


def _field(tcfg, name: str, default=None):
    """A field of a model config, given as the config or as its dict."""
    return tcfg.get(name, default) if isinstance(tcfg, dict) else getattr(tcfg, name, default)


def _mixers(tcfg) -> list:
    """The mixer of each layer of a model config (or its dict) that names them."""
    return list(_field(tcfg, "mixer_layers") or ())


def _caching_layers(tcfg, L: int) -> int:
    """Layers whose cache grows with the sequence: those with attention."""
    mixers = _mixers(tcfg)
    return L - sum(m in ("delta", "ssm", "conv", "none") for m in mixers)


def recurrent_state_bytes(tcfg, rows: int, conv_size: int = 2) -> int:
    """What the layers with a recurrent state of a model config (or its dict)
    keep for `rows` rows whatever their length, a float32 state and the last
    inputs of their convolutions (compute dtype): a delta-rule layer [heads,
    d, d] a value head and `delta_conv - 1` inputs of q, k and v, a state-space
    layer [heads, head_dim, ssm_state] and `ssm_conv - 1` inputs of one (x',
    B and C wide), a short-convolution layer `conv_taps - 1` inputs of one
    (hidden_size wide) and no state. 0 for a model without such layers."""
    H, D = int(_field(tcfg, "delta_heads", 0)), int(_field(tcfg, "delta_head_dim", 0))
    Hv = int(_field(tcfg, "delta_value_heads") or H)  # value heads: one state each
    conv = (int(_field(tcfg, "delta_conv", 4)) - 1) * (2 * H + Hv) * D
    total = _mixers(tcfg).count("delta") * rows * (4 * Hv * D * D + conv_size * conv)
    sh, sp, sn = (int(_field(tcfg, k, 0)) for k in ("ssm_heads", "ssm_head_dim", "ssm_state"))
    tail = (int(_field(tcfg, "ssm_conv", 4)) - 1) * (sh * sp + 2 * int(_field(tcfg, "ssm_groups", 1)) * sn)
    total += _mixers(tcfg).count("ssm") * rows * (4 * sh * sp * sn + conv_size * tail)
    short = (int(_field(tcfg, "conv_taps", 3)) - 1) * int(_field(tcfg, "hidden_size", 0))
    return total + _mixers(tcfg).count("conv") * rows * conv_size * short


def _recurrent_note(tcfg) -> str:
    kinds = [f"{_mixers(tcfg).count(m)} {name}"
             for m, name in (("delta", "delta-rule"), ("ssm", "state-space"), ("conv", "short-convolution"))
             if m in _mixers(tcfg)]
    state = "float32 state and " if {"delta", "ssm"} & set(_mixers(tcfg)) else ""
    return " and ".join(kinds) + f" layers: {state}convolution inputs a row, whatever its length"


def _family_param_count(tcfg: Dict[str, Any]) -> int:
    """Parameters HELD HERE of a model with latent attention, delta-rule or
    state-space layers, layers of one sub-layer, routed experts (this chip's
    share: `n_experts_held` of the `n_routed_experts` the router scores; in a
    latent space, gated or not) and several residual streams, from the
    config's numbers alone."""
    V, E, L = int(tcfg["vocab_size"]), int(tcfg["hidden_size"]), int(tcfg["n_layer"])
    H, I = int(tcfg["n_head"]), int(tcfg.get("intermediate_size", 4 * E))
    gated = 3 if tcfg.get("mlp_gated") else 2
    rank = tcfg.get("kv_lora_rank")
    if rank:
        dn, dr, dv = (int(tcfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        qr = int(tcfg.get("q_lora_rank") or 0)
        q = E * qr + qr + qr * H * (dn + dr) if qr else E * H * (dn + dr)
        attn = q + E * (rank + dr) + rank + rank * H * (dn + dv) + H * dv * E
    else:
        D = int(tcfg.get("head_dim") or E // max(H, 1))
        attn = 2 * E * D * (H + int(tcfg.get("n_kv_head") or H))  # q and o; k and v
        attn += E * D * H if tcfg.get("attn_output_gate") else 0  # q's gate columns
        attn += 2 * D if tcfg.get("qk_norm") else 0  # a weight a head's channel, q's and k's
    n = int(tcfg.get("residual_streams", 1))
    mix = 2 * (n * E * (n * n + 2 * n) + 3 + 2 * n + n * n) if n > 1 else 0
    mixers = list(tcfg.get("mixer_layers") or [None] * L)
    by_mixer = {None: attn, "softmax": attn, "latent": attn, "none": 0}
    if "delta" in mixers:
        # q, k, v, o; two low-rank pairs through d; beta; the taps; A_log, dt_bias, the output norm
        kh, kd, taps = int(tcfg["delta_heads"]), int(tcfg["delta_head_dim"]), int(tcfg.get("delta_conv", 4))
        w = kh * kd
        if tcfg.get("delta_form") == "gated":
            # W_qkvz, W_ba, W_o; the taps over q, k and v; A_log, dt_bias, the output norm
            vh = int(tcfg.get("delta_value_heads") or kh)
            wv, conv = vh * kd, 2 * w + vh * kd
            by_mixer["delta"] = E * (conv + wv) + 2 * E * vh + wv * E + conv * taps + 2 * vh + kd
        else:
            by_mixer["delta"] = 4 * E * w + 2 * (E * kd + kd * w) + E * kh + 3 * w * taps + kh + w + kd
    if "ssm" in mixers:
        # both projections; the taps and their bias; A_log, D, dt_bias; the gated norm
        sh, w = int(tcfg["ssm_heads"]), int(tcfg["ssm_heads"]) * int(tcfg["ssm_head_dim"])
        conv = w + 2 * int(tcfg.get("ssm_groups", 1)) * int(tcfg["ssm_state"])
        by_mixer["ssm"] = (E * (w + conv + sh) + w * E + conv * (int(tcfg.get("ssm_conv", 4)) + 1)
                           + 3 * sh + w)
    if "conv" in mixers:
        # W_in (B, C and u) and W_out; the taps
        by_mixer["conv"] = 4 * E * E + int(tcfg.get("conv_taps", 3)) * E
    attn = [by_mixer[m] for m in mixers]
    published = int(tcfg.get("n_routed_experts", 0))
    lead = int(tcfg.get("first_k_dense", 0)) if published else L
    feed = [gated * E * I] * lead
    if published:
        F = int(tcfg["moe_intermediate_size"])
        held = int(tcfg.get("n_experts_held") or published)
        matrices = 3 if tcfg.get("moe_gated", True) else 2
        latent = int(tcfg.get("moe_latent_size") or 0)
        shared = int(tcfg.get("moe_shared_intermediate_size") or F * int(tcfg.get("n_shared_experts", 0)))
        softmax = tcfg.get("router_score") == "softmax"
        feed += [E * published + (0 if softmax else published)  # router and its bias
                 + matrices * (latent or E) * F * held + 2 * E * latent  # the held experts, the latent pair
                 + (matrices * E * shared if tcfg.get("n_shared_experts") else 0)
                 + (E if tcfg.get("shared_expert_gate") else 0)] * (L - lead)
    # a layer of one sub-layer has the norm of what it has
    ffns = list(tcfg.get("ffn_layers") or ["full"] * L)
    feed = [0 if kind == "none" else size for kind, size in zip(ffns, feed)]
    norms = sum((m != "none") + (f != "none") for m, f in zip(mixers, ffns)) * E
    heads = 1 if tcfg.get("tie_word_embeddings", True) else 2  # a tied head is the embedding
    return heads * V * E + sum(attn) + sum(feed) + norms + L * mix + E


def analytic_plan(
    config,
    hbm_bytes: int = 0,
    devices: int = 0,
) -> HBMPlan:
    """Per-phase HBM plan from a TRLConfig ALONE — no trainer, no
    device, no allocation (the scripts/hbm_plan.py path). Uses
    :func:`analytic_param_count` for the state trees and the same
    activation/pool formulas as :func:`estimate_plan`.

    ``devices`` resolves auto mesh axes (``-1`` = absorb remaining
    devices — unknowable offline): with it, the -1 axis becomes
    ``devices // (product of fixed axes)``; without it, the axis is
    assumed 1 and the plan carries a loud note (per-device rows are
    then WORST-CASE for any real device count)."""
    train = config.train
    mcfg = MemoryConfig.from_dict(getattr(train, "memory", None))
    tdict = (config.model.model_extra_configs or {}).get("transformer", {})
    tdict = dict(tdict)
    tdict.setdefault("n_positions", train.seq_length)
    n_params = analytic_param_count(tdict)
    E = int(tdict.get("hidden_size", 768))
    L = int(tdict.get("n_layer", 12))
    V = int(tdict.get("vocab_size", 50257))
    H = int(tdict.get("n_head", 12))
    Hkv = int(tdict.get("n_kv_head", H))
    D = int(tdict.get("head_dim", E // max(H, 1)))

    mesh = dict(train.mesh)
    auto_axes = [ax for ax, s in mesh.items() if s == -1]
    if auto_axes:
        fixed = 1
        for ax, s in mesh.items():
            if s > 0:
                fixed *= s
        resolved = max(devices // fixed, 1) if devices else 1
        # one -1 axis absorbs the remainder; any extras degenerate to 1
        mesh[auto_axes[0]] = resolved
        for ax in auto_axes[1:]:
            mesh[ax] = 1
    ways = max(mesh.get("dp", 1) * mesh.get("fsdp", 1), 1)
    shard = max(mesh.get("fsdp", 1), 1)  # state trees: fsdp only

    plan = HBMPlan(
        budget_bytes=hbm_bytes or mcfg.hbm_bytes or device_hbm_bytes(),
        headroom=mcfg.headroom,
    )
    if auto_axes and not devices:
        plan.add(
            "host", "mesh_note", 0,
            f"mesh axis {auto_axes[0]!r} is -1 (absorb devices) and no "
            "--devices was given: per-device rows assume ONE device on "
            "that axis — worst case for any real device count",
        )
    psize = _dtype_size(train.param_dtype)
    plan.add("steady", "params", n_params * psize // shard,
             f"~{n_params / 1e6:.1f}M params (analytic)")
    opt_name = config.optimizer.name.lower()
    # adam8bit: m AND v as int8 payloads + fp32 per-block absmax scales
    # (block 256, ops/adam8bit.py) ~= 2 + 8/256 bytes/param — call it 3
    # to absorb padding; full-precision adam: two fp32 moments
    opt_mult = 3 if "8bit" in opt_name or "adam8" in opt_name else 8
    plan.add("steady", "opt_state", n_params * opt_mult // shard,
             f"{config.optimizer.name} (x{opt_mult} bytes/param"
             + (": 2x int8 moments + block scales)" if opt_mult == 3 else ")"))
    unfrozen = config.model.num_layers_unfrozen
    mname = getattr(config.method, "name", "").lower()
    if mname in ("ppoconfig", "ppo"):
        ref_frac = 1.0 if unfrozen in (-1, None) else min(
            max(unfrozen, 0) / max(L, 1), 1.0
        )
        plan.add("steady", "ref_params", int(n_params * psize * ref_frac) // shard,
                 "frozen reference" + (" (hydra branch)" if ref_frac < 1 else ""))
    elif mname in ("grpoconfig", "grpo", "dpoconfig", "dpo"):
        # GRPO keeps a deep-copied initial policy for the in-loss KL;
        # DPO a frozen reference for the logprob margin — both FULL
        # copies (omitting them under-planned a whole model)
        plan.add("steady", "ref_params", n_params * psize // shard,
                 "frozen reference (full copy of the initial policy)")

    mb = train.minibatch_size or train.batch_size
    rows_dev = max(mb // ways, 1)
    S = train.seq_length
    csize = _dtype_size(train.compute_dtype)
    streams = int(tdict.get("residual_streams", 1))
    plan.add("train", "activations",
             activation_bytes(rows_dev, S, E * streams, L, train.remat_policy, csize),
             f"mb_size {mb}, remat {train.remat_policy!r} "
             f"(coeff {_act_coeff(train.remat_policy):g})"
             + (f", {streams} residual streams" if streams > 1 else ""))
    gsize = _dtype_size(train.grads_dtype or train.param_dtype)
    plan.add("train", "grads", n_params * gsize // shard,
             f"dtype {train.grads_dtype or train.param_dtype}")
    chunks = max(int(train.logit_chunks or 0), 0)
    plan.add("train", "logits", logits_bytes(rows_dev, S, V, chunks),
             "full materialization — set train.logit_chunks"
             if chunks == 0 else f"chunked x{chunks}")
    n_rows = int(getattr(config.method, "num_rollouts", train.batch_size))
    plan.add("train", "epoch_batch", epoch_batch_bytes(n_rows, S, ways),
             "device-resident rollout store (fused_inner_loop)")

    plan.add("rollout", "decode_params", n_params * 2,
             "bf16 decode cast copy")
    ge = dict(getattr(config.method, "gen_engine", None) or {})
    chunk = int(getattr(config.method, "chunk_size", train.batch_size))
    gen_kwargs = dict(getattr(config.method, "gen_kwargs", {}) or {})
    max_new = int(gen_kwargs.get("max_new_tokens", 40))
    if ge.get("enabled"):
        from trlx_tpu.models.gen_engine import GenEngineConfig

        class _MC:  # the handful of fields resolve()/pool-bytes read
            n_layer = L
            n_kv_head = Hkv
            head_dim = D
            kv_cache_quant = tdict.get("kv_cache_quant")
            dtype = train.compute_dtype

        spec = GenEngineConfig.from_dict(ge).resolve(chunk, _MC)
        pool_b = engine_pool_bytes(spec, _MC, max(S - max_new, 1), max_new)
        plan.add("rollout", "engine_kv_pool", pool_b,
                 f"{spec.slots} slots, page_size {spec.page_size}, "
                 f"quant {spec.kv_quant or 'none'}")
        if spec.spec_decode:
            from trlx_tpu.models.gen_engine import hydra_shared_trunk_layers

            sh = hydra_shared_trunk_layers(
                L, int(getattr(config.model, "num_layers_unfrozen", -1))
            )
            plan.add(
                "rollout", "engine_draft_pool",
                draft_pool_bytes(pool_b, L, sh),
                f"draft branch layers only ({sh} trunk layers share the "
                "policy pool)" if sh else "speculative draft pool (full copy)",
            )
    else:
        kv_quant = tdict.get("kv_cache_quant")
        kv_size = 1 if kv_quant == "int8" else 2
        latent = tdict.get("kv_lora_rank")
        per_position = (int(latent) + int(tdict.get("qk_rope_head_dim", 0))) if latent else 2 * Hkv * D
        caching = _caching_layers(tdict, L)
        plan.add("rollout", "static_kv_cache",
                 int(caching * chunk * S * per_position * kv_size),
                 f"whole-chunk cache, {per_position} numbers a position a layer"
                 + (" (latent)" if latent else "")
                 + (f", the {caching} of {L} layers that cache" if caching < L else "")
                 + f", quant {kv_quant or 'none'}")
        state_b = recurrent_state_bytes(tdict, chunk)
        if state_b:
            plan.add("rollout", "recurrent_state", state_b, _recurrent_note(tdict))

    exp = dict(getattr(config.method, "exp", None) or {})
    if exp.get("enabled"):
        depth = int(exp.get("max_depth", 4) or 4)
        plan.add("host", "exp_queue", epoch_batch_bytes(depth * chunk, S, 1),
                 f"experience transport, max_depth {depth}")
    fleet = dict(getattr(config.method, "fleet", None) or {})
    if fleet.get("enabled"):
        plan.add("host", "fleet_broadcast", n_params * psize,
                 "one host param copy per weight publish")
    return plan
