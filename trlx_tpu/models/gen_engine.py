"""Serving-grade rollout decode engine: continuous batching over a
paged (optionally int8) KV cache, with reference-drafted speculative
decoding.

The static sampler (models/generation.py) steps the WHOLE batch until
every row finishes: one long response stalls the batch, and by the tail
of the loop a single live row pays a full-width decode step. This
engine replaces that loop for rollout collection with a slot-based
design:

  * **Continuous batching** — a fixed set of `slots` decode lanes is
    fed from a device-resident prompt queue. The whole queue is
    processed by ONE jitted `lax.while_loop`: whenever a lane finishes
    (EOS / its token budget), the next iteration's refill phase
    (`lax.cond`, so it costs nothing on iterations with no refill)
    prefills the next queued prompt INTO that slot and decoding
    continues at full occupancy. `queue size >> slots` is the intended
    shape: the step batch stays dense for the whole rollout phase
    instead of decaying to one live row.
  * **Paged int8 KV** (ops/paged_kv.py) — slots index fixed-size pages
    through a page table; a refilled slot's pages return to a free
    stack and are reused, and response pages are allocated lazily, so
    short responses never pay max-length KV. `paged=False` keeps the
    indirection out (a contiguous per-slot layout the gather collapses
    through) so the two pillars are separable in benchmarks.
  * **Speculative decoding** — a draft model (the frozen PPO reference:
    the policy is one KL-constrained step away from it, so acceptance
    is high) drafts `draft_k` tokens autoregressively; the policy
    verifies all of them in ONE `T=draft_k` forward (one weight read
    amortized over k tokens) with standard rejection sampling, which
    leaves the sampled distribution exactly the policy's. Greedy mode
    accepts iff the draft token equals the policy argmax, so greedy
    output is token-for-token the non-speculative stream.

RNG contract: every sampling event is keyed on (queue row, response
index, event kind) folded into the call's base key — NOT on the slot or
the step. A prompt therefore samples the same continuation regardless
of batch composition, slot assignment, refill order, or whether
speculative decoding is enabled (when draft == policy, acceptance is
certain and the streams are bit-identical). tests/test_gen_engine.py
pins all of these.

Scope (v1): causal LMs, single data group (the rollout-worker geometry
of the disaggregated actor–learner plan — ROADMAP item 1); no soft
prompts / prefix tuning; multihost and seq2seq fall back to the static
sampler in trainer/base.generate.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from trlx_tpu.models.generation import (
    SamplerSettings,
    cast_params_for_decode,
    categorical_lanes,
    lane_keys,
    process_logits,
    sample_token_lanes,
)
from trlx_tpu.models.transformer import TransformerLM, logit_projection
from trlx_tpu.ops import paged_kv

Array = jnp.ndarray


@dataclass(frozen=True)
class GenEngineConfig:
    """`ppo.gen_engine.*` — user-facing engine configuration (plain dict
    in YAML; parsed here so unknown keys fail at config load).

    enabled       route PPO rollout generation through the engine
                  (default off: byte-identical rollouts to the static
                  sampler's RNG stream are NOT preserved across the
                  switch — the engine keys RNG per (prompt, position)).
    slots         decode lanes per step; 0 = the generate() call's
                  batch width (chunk size), i.e. refills only help a
                  ragged tail. Real wins come from slots < chunk.
    page_size     tokens per KV page.
    paged         False = contiguous per-slot layout (no indirection,
                  no lazy allocation — the continuous-batching-only
                  configuration benchmarks attribute against).
    pool_pages    total pages in the pool; 0 = worst case
                  (slots * pages_per_slot + null page), which can only
                  be undersized deliberately.
    refill_width  prompts prefilled per refill event; 0 = slots.
    spec_decode   draft with the frozen reference, verify with the
                  policy (exact via rejection sampling).
    draft_k       drafted tokens per speculative round.
    kv_quant      "int8" | "none"; None follows the model's
                  kv_cache_quant (the production rollout default).
    paged_attention_impl  "xla" (gather path) | "pallas" (the paged
                  decode kernel: pages stream from the pool via the
                  page table as block index map — nothing S-wide is
                  ever gathered). Applies to the paged layout only;
                  the contiguous layout always takes the XLA path (its
                  gather is already a fused reshape). On TPU the
                  pallas impl needs page_size % 128 == 0.
    data_groups   independent engine LANE GROUPS per call: the queue
                  splits into this many shards, each with its own
                  slots/pool/page-table/allocator, run as one stacked
                  dispatch (group state shards over the mesh's data
                  axes when the geometry divides). RNG stays keyed on
                  the GLOBAL queue row, so greedy output is
                  token-for-token the single-group stream.
    """

    enabled: bool = False
    slots: int = 0
    page_size: int = 128
    paged: bool = True
    pool_pages: int = 0
    refill_width: int = 0
    spec_decode: bool = False
    draft_k: int = 4
    kv_quant: Optional[str] = None
    paged_attention_impl: str = "xla"
    data_groups: int = 1

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "GenEngineConfig":
        d = dict(d or {})
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"ppo.gen_engine: unknown keys {sorted(unknown)} "
                f"(known: {sorted(known)})"
            )
        cfg = cls(**d)
        if cfg.page_size < 1:
            raise ValueError("ppo.gen_engine.page_size must be >= 1")
        if cfg.draft_k < 1:
            raise ValueError("ppo.gen_engine.draft_k must be >= 1")
        if cfg.kv_quant not in (None, "none", "int8"):
            raise ValueError(
                f"ppo.gen_engine.kv_quant must be none/int8, got {cfg.kv_quant!r}"
            )
        if cfg.paged_attention_impl not in ("xla", "pallas"):
            raise ValueError(
                "ppo.gen_engine.paged_attention_impl must be xla/pallas, "
                f"got {cfg.paged_attention_impl!r}"
            )
        if cfg.data_groups < 1:
            raise ValueError("ppo.gen_engine.data_groups must be >= 1")
        return cfg

    def resolve(self, batch: int, model_cfg) -> "EngineSpec":
        """Concretize against a call's batch width and the model."""
        quant = self.kv_quant
        if quant is None:
            quant = "int8" if model_cfg.kv_cache_quant == "int8" else "none"
        slots = self.slots or batch
        if batch:
            slots = min(slots, batch)
        groups = self.data_groups
        if batch:
            groups = max(1, min(groups, batch))
        return EngineSpec(
            slots=slots,
            page_size=self.page_size,
            paged=self.paged,
            pool_pages=self.pool_pages,
            refill_width=self.refill_width or slots,
            spec_decode=self.spec_decode,
            draft_k=self.draft_k,
            kv_quant=None if quant == "none" else quant,
            paged_attention_impl=self.paged_attention_impl,
            data_groups=groups,
        )


@dataclass(frozen=True)
class EngineSpec:
    """Static engine geometry (hashable: keys the jit cache).

    ``draft_shared_layers`` is DERIVED, not user config: with a hydra
    (policy-trunk + frozen-branch) speculative draft, the draft's
    bottom ``draft_shared_layers`` layers are the policy's trunk — the
    trainer sets it from the composed reference's branch depth so the
    engine stores trunk KV ONCE (the pool's layer axis extends by only
    the branch depth instead of doubling; see engine_generate). It is
    only valid when ``compose_draft_params`` built the draft — a
    full-copy draft shares nothing and must leave it 0."""

    slots: int
    page_size: int = 128
    paged: bool = True
    pool_pages: int = 0
    refill_width: int = 0
    spec_decode: bool = False
    draft_k: int = 4
    kv_quant: Optional[str] = None
    paged_attention_impl: str = "xla"
    data_groups: int = 1
    draft_shared_layers: int = 0


def hydra_shared_trunk_layers(n_layer: int, ref_branch_layers) -> int:
    """Trunk layers a composed hydra draft shares with the policy pool:
    ``L - k`` when the frozen reference is a top-``k`` branch
    (0 < k < L); 0 for a full-copy reference (its layers all diverge
    from the policy's the moment training moves) and for k == 0. The
    ONE derivation shared by the trainer (`_engine_spec`) and the
    memory-doctor planners, so the spec the jit traces and the bytes
    the preflight admits can't disagree."""
    k = ref_branch_layers
    if k is None or k <= 0 or k >= n_layer:
        return 0
    return n_layer - k


def _round_up(x: int, to: int) -> int:
    return x + (-x) % to


def compose_draft_params(cfg, policy_params: Dict, ref_params: Dict) -> Dict:
    """The speculative draft model = the frozen PPO reference.

    With a full-copy reference (num_layers_unfrozen=-1) the reference IS
    a standalone model — return it. With a hydra branch the reference is
    only the top-k layers; the draft composes the policy's trunk (the
    bottom layers are shared and frozen-equivalent at the branch point)
    with the frozen branch into a full stack. The concat materializes a
    trunk copy inside the trace — acceptable per generate call at small
    scale; at multi-GB scale prefer a full-copy reference when drafting.
    """
    k = jax.tree_util.tree_leaves(ref_params["blocks"])[0].shape[0]
    if k == cfg.n_layer:
        return ref_params
    trunk = jax.tree_util.tree_map(
        lambda x: x[: cfg.n_layer - k], policy_params["blocks"]
    )
    blocks = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b.astype(a.dtype)], axis=0),
        trunk, ref_params["blocks"],
    )
    return dict(ref_params, blocks=blocks)


def engine_generate(
    model: TransformerLM,
    params: Dict,
    q_ids: Array,  # [Q, P] int32, LEFT-padded prompt queue
    q_mask: Array,  # [Q, P] int32
    rng: jax.Array,
    settings: SamplerSettings,
    spec: EngineSpec,
    draft_params: Optional[Dict] = None,
    row_budget: Optional[Array] = None,  # [Q] per-row max_new (<= N)
    warm: Optional[Dict[str, Array]] = None,
    q_pin: Optional[Array] = None,  # [Q] bool: keep pages at finish
    q_ready: Optional[Array] = None,  # [Q] page-aligned shared prefix len
    q_rng_row: Optional[Array] = None,  # [Q] per-row RNG id base
    rng_space: Optional[int] = None,  # id-space width (default Q): the
    # GLOBAL queue size when this call serves one shard of a grouped
    # run, so the acceptance/residual RNG offsets match the
    # single-group stream exactly
) -> Dict[str, Array]:
    """Generate a continuation for every queue row through the engine.

    Returns the static sampler's output contract (sequences [Q, P+N],
    response_ids [Q, N], response_mask [Q, N]) plus `gen_stats`, a dict
    of device scalars: decode_steps, refills, real_tokens,
    occupancy (real tokens / (decode_steps * slots)), truncated (rows
    that hit their budget without EOS), oom_truncated (lanes killed by
    page-pool exhaustion — 0 unless pool_pages was undersized),
    reclaimed_pages (prompt-pad compaction: pages holding nothing but
    left-pad KV, released back to the free stack at refill), and in
    speculative mode drafted / accepted / spec_rounds.

    Serving mode (``warm`` given — the trlx_tpu/serve/ tier): the call
    enters with a PERSISTENT page pool instead of a fresh one.
    ``warm`` carries ``pool`` (pre-populated leaves), ``free``/``ntop``
    (the host's free stack, minus every page a cached prefix/session
    entry holds), ``refcnt`` (per-page counts, paged_kv.init_refcounts
    contract) and ``row_table`` [Q, MP] (each row's shared-page
    mapping; entries past ``q_ready[q] // page_size`` must be 0).
    A row with ``q_ready[q] = A`` has its first A slot positions
    already present in shared pages: refill maps those pages
    read-only, pops fresh pages only for the rest, and the prefill
    scatter is gated off positions < A (copy-on-write: the divergent
    suffix always lands in the row's own pages). Rows with
    ``q_pin[q]`` keep ALL their pages at finish — the final table row
    and KV length come back in ``kv_state.saved_tables`` /
    ``saved_len`` for the host to adopt into its session/prefix cache
    — and are counted in ``gen_stats.pinned_pages``, NEVER in
    ``reclaimed_pages`` or ``oom_truncated`` (a pin is a normal
    finish, not a truncation, and the pages are alive, not reclaimed).
    ``q_rng_row`` replaces the queue index in the RNG id space so a
    request's sampled stream is invariant to which call/batch serves
    it. The output gains ``kv_state`` = the end-of-call pool + free
    stack + refcounts for the host to carry into the next call.
    """
    Q, P = q_ids.shape
    N = settings.max_new_tokens
    if N < 1:
        raise ValueError("max_new_tokens must be >= 1")
    cfg = model.cfg
    SLOTS = max(1, min(spec.slots, Q))
    PS = spec.page_size
    K = spec.draft_k if spec.spec_decode else 0
    # speculative rounds may draft past a lane's budget before the
    # verifier truncates; give every slot K slack positions so those
    # writes land in real (masked, later-cleared) slots
    MP = paged_kv.pages_per_slot(P, N + K, PS)
    S = MP * PS
    PP = -(-P // PS)  # prompt pages per refill (pads included)
    # contiguous layout needs its full static page range; only the
    # paged layout can run on a deliberately undersized pool
    NP = (spec.pool_pages or (1 + SLOTS * MP)) if spec.paged else (
        1 + SLOTS * MP
    )
    if NP < 1 + SLOTS * PP:
        raise ValueError(
            f"pool_pages={NP} cannot hold {SLOTS} slots' prompts "
            f"({PP} pages each + null page)"
        )
    R = max(1, min(spec.refill_width or SLOTS, SLOTS))
    quant = spec.kv_quant
    eos = jnp.int32(settings.eos_token_id)
    pad = jnp.int32(settings.pad_token_id)
    if spec.spec_decode and draft_params is None:
        raise ValueError("spec_decode needs draft_params (the reference)")
    # spec-decode trunk-KV sharing (hydra draft = policy trunk + frozen
    # branch): the draft's trunk KV is IDENTICAL to the policy's by
    # construction — same weights, same token inputs, same positions —
    # so instead of a full second pool the ONE pool's layer axis
    # extends by just the draft's BRANCH depth. Trunk pages are held
    # once; the pool refcounts account for the two logical holders
    # (policy stream + draft stream) of every page.
    shared = spec.draft_shared_layers if spec.spec_decode else 0
    if shared:
        if not 0 < shared < cfg.n_layer:
            raise ValueError(
                f"draft_shared_layers={shared} must be in (0, n_layer="
                f"{cfg.n_layer})"
            )
        KB = cfg.n_layer - shared  # draft branch layers stored past L
        draft_layer_ixs = jnp.concatenate(
            [
                jnp.arange(shared, dtype=jnp.int32),
                cfg.n_layer + jnp.arange(KB, dtype=jnp.int32),
            ]
        )
    else:
        KB = 0
        draft_layer_ixs = None
    pool_layers = cfg.n_layer + KB
    # every spec-decode page is held by BOTH streams (trunk layers by
    # construction; branch layers ride the same physical page of the
    # extended pool), so page lifetime runs through the refcount
    # machinery: +2 at allocation, two decrements at release
    refcounted = spec.spec_decode and spec.paged
    serving = warm is not None
    if serving:
        if not spec.paged:
            raise ValueError("serving (warm pool) requires spec.paged")
        if spec.spec_decode:
            raise ValueError(
                "serving (warm pool) does not compose with spec_decode "
                "in v1 (the draft pool has no shared-page story yet)"
            )
        if q_pin is None:
            q_pin = jnp.zeros((Q,), bool)
        q_pin = q_pin.astype(bool)
        if q_ready is None:
            q_ready = jnp.zeros((Q,), jnp.int32)
        q_ready = q_ready.astype(jnp.int32)

    params = cast_params_for_decode(params, cfg.dtype)
    from trlx_tpu.parallel.sharding import unshard_for_decode

    params = unshard_for_decode(params, getattr(model, "mesh", None))
    if getattr(cfg, "decode_weights_quant", None) == "int8":
        from trlx_tpu.models.transformer import quantize_decode_weights

        params = quantize_decode_weights(params)
    if draft_params is not None:
        draft_params = cast_params_for_decode(draft_params, cfg.dtype)
        draft_params = unshard_for_decode(
            draft_params, getattr(model, "mesh", None)
        )
        if getattr(cfg, "decode_weights_quant", None) == "int8":
            from trlx_tpu.models.transformer import quantize_decode_weights

            draft_params = quantize_decode_weights(draft_params)

    q_ids = q_ids.astype(jnp.int32)
    q_mask = q_mask.astype(jnp.int32)
    if row_budget is None:
        row_budget = jnp.full((Q,), N, jnp.int32)
    row_budget = jnp.clip(row_budget.astype(jnp.int32), 1, N)

    # RNG id spaces: token draws at r*N + j; acceptance and residual
    # draws in disjoint ranges above them. rng_space widens the id
    # space to the GLOBAL queue size under grouped lanes, so a shard's
    # offsets land exactly where the single-group run's do.
    Qr = rng_space or Q
    OFF_ACC = (Qr + 1) * N
    OFF_RES = 2 * (Qr + 1) * N

    def _rng_ids(ix: Array) -> Array:
        """RNG id base per queue row: the queue index by default; the
        caller-supplied per-request id in serving mode, so a request's
        sampled stream is invariant to batch composition across calls."""
        if q_rng_row is None:
            return ix
        return q_rng_row.astype(jnp.int32)[jnp.clip(ix, 0, Q - 1)]

    # pallas prefill wants a 128-aligned temp cache + 8-row-aligned
    # queries, mirroring generate()'s gate; otherwise it falls back to
    # XLA inside the same code path
    Pc = _round_up(P, 128) if (cfg.attention_impl == "pallas" and P % 8 == 0) else P

    def _contig_table() -> Array:
        base = 1 + jnp.arange(SLOTS * MP, dtype=jnp.int32).reshape(SLOTS, MP)
        return base

    def _init_state() -> Dict[str, Any]:
        if serving:
            state: Dict[str, Any] = {"pool": dict(warm["pool"])}
            state["free"] = warm["free"]
            state["ntop"] = warm["ntop"].astype(jnp.int32)
            state["refcnt"] = warm["refcnt"].astype(jnp.int32)
            state["table"] = jnp.zeros((SLOTS, MP), jnp.int32)
            state["saved_tables"] = jnp.zeros((Q, MP), jnp.int32)
            state["saved_len"] = jnp.zeros((Q,), jnp.int32)
            state["pinned"] = jnp.int32(0)
        else:
            pool = paged_kv.init_pool(
                pool_layers, NP, PS, cfg.n_kv_head, cfg.head_dim, quant,
                cfg.dtype,
            )
            state = {"pool": pool}
            if spec.spec_decode and not shared:
                # full-copy draft: nothing is shared — it keeps its own
                # full-depth pool over the same page ids (the historic
                # 2x layout, now only paid when it is actually needed)
                state["dpool"] = paged_kv.init_pool(
                    cfg.n_layer, NP, PS, cfg.n_kv_head, cfg.head_dim, quant,
                    cfg.dtype,
                )
            if spec.paged:
                free, ntop = paged_kv.init_alloc(NP)
                state["free"], state["ntop"] = free, ntop
                state["table"] = jnp.zeros((SLOTS, MP), jnp.int32)
                if refcounted:
                    state["refcnt"] = paged_kv.init_refcounts(NP)
            else:
                state["table"] = _contig_table()
        state.update(
            pos=jnp.zeros((SLOTS,), jnp.int32),
            npad=jnp.zeros((SLOTS,), jnp.int32),
            new=jnp.zeros((SLOTS,), jnp.int32),
            budget=jnp.ones((SLOTS,), jnp.int32),
            active=jnp.zeros((SLOTS,), bool),
            pidx=jnp.zeros((SLOTS,), jnp.int32),
            cur=jnp.zeros((SLOTS,), jnp.int32),
            kmask=jnp.zeros((SLOTS, S), jnp.int32),
            qnext=jnp.int32(0),
            resp_ids=jnp.full((Q, N), pad, jnp.int32),
            resp_mask=jnp.zeros((Q, N), jnp.int32),
            decode_steps=jnp.int32(0),
            lane_steps=jnp.int32(0),
            refills=jnp.int32(0),
            emitted=jnp.int32(0),
            truncated=jnp.int32(0),
            oom=jnp.int32(0),
            reclaimed=jnp.int32(0),
            rounds=jnp.int32(0),
            drafted=jnp.int32(0),
            accepted=jnp.int32(0),
        )
        return state

    def _paged_cache(pool, state, slot_pos, key_mask, draft=False):
        cache = dict(
            pool,
            page_table=state["table"],
            slot_pos=slot_pos,
            key_mask=key_mask,
            lane_valid=state["active"],
        )
        if not spec.paged:
            cache["contiguous"] = True
        elif spec.paged_attention_impl != "xla":
            cache["attn_impl"] = spec.paged_attention_impl
        if draft and shared:
            # the draft's trunk layers read/write the POLICY pool's
            # trunk slots; its branch layers the extension slots
            cache["layer_ixs"] = draft_layer_ixs
        return cache

    def _draft_pool(state):
        return state["pool"] if shared else state["dpool"]

    def _with_draft_pool(state, pool):
        return dict(state, pool=pool) if shared else dict(state, dpool=pool)

    def _note_alloc(state, ids):
        """Freshly popped pages enter with refcount 2 in spec-decode
        mode: one hold per stream (policy + draft) of the page."""
        if not refcounted:
            return state
        return dict(
            state,
            refcnt=state["refcnt"].at[ids].add(
                2 * (ids > 0).astype(jnp.int32)
            ),
        )

    def _free_slot_pages(state, pages, is_real):
        """Return pages to the free stack. Spec-decode mode releases
        through the refcount machinery — one decrement per stream, the
        second (count-zero) release pushes the page — so trunk pages
        are provably held ONCE and `free + held == pool` balances."""
        if refcounted:
            free, ntop, rc = paged_kv.release_refcounted(
                state["free"], state["ntop"], state["refcnt"], pages, is_real
            )
            free, ntop, rc = paged_kv.release_refcounted(
                free, ntop, rc, pages, is_real
            )
            return dict(state, free=free, ntop=ntop, refcnt=rc)
        free, ntop = paged_kv.push_free(
            state["free"], state["ntop"], pages, is_real
        )
        return dict(state, free=free, ntop=ntop)

    def _prefill_into_slots(
        prms, pool, state, ids, mask, posns, slot, do, ready=None,
        branch_only=False,
    ):
        """Dense prefill of [R, P] prompts, scattered into `slot`'s
        pages. Returns (pool, last_hidden [R, E]). ``ready`` [R] gates
        the scatter off slot positions < ready (serving: those
        positions live in SHARED pages, already prefilled by the
        request that created the cache entry — this v1 recomputes their
        KV transiently in the temp cache but never writes it, which is
        what makes the shared pages safely read-only). ``branch_only``
        (trunk-sharing draft prefill) scatters just the draft's BRANCH
        layers into the pool's extension slots: its trunk KV is the
        policy prefill's, already written."""
        key_mask = jnp.concatenate(
            [mask, jnp.zeros((R, Pc - P), jnp.int32)], axis=1
        ) if Pc != P else mask
        tmp = model.init_cache(R, Pc, key_mask)
        out = model(
            prms, ids, mask, positions=posns, cache=tmp, compute_logits=False
        )
        ck = out["cache"]["k"][:, :, :P]  # [L, R, P, Hkv, D]
        cv = out["cache"]["v"][:, :, :P]
        lsel = None
        if branch_only:
            ck = ck[cfg.n_layer - KB:]
            cv = cv[cfg.n_layer - KB:]
            lsel = cfg.n_layer + jnp.arange(KB, dtype=jnp.int32)
        elif shared:
            # extended pool: the policy stack fills layers 0..L-1, the
            # extension slots belong to the draft branch
            lsel = jnp.arange(cfg.n_layer, dtype=jnp.int32)
        tbl = state["table"][jnp.clip(slot, 0, SLOTS - 1)]
        prompt_pos = jnp.broadcast_to(
            jnp.arange(P, dtype=jnp.int32)[None, :], (R, P)
        )
        pids, offs = paged_kv.write_positions(tbl, prompt_pos, PS, lane_valid=do)
        if ready is not None:
            # copy-on-write boundary: shared positions route to the
            # null page (their KV is already in the shared pages)
            pids = jnp.where(prompt_pos < ready[:, None], 0, pids)
        if quant == "int8":
            kq, ks = paged_kv.quantize_rows(ck)
            vq, vs = paged_kv.quantize_rows(cv)
            pool = dict(
                pool,
                pk=paged_kv.scatter_prefill(
                    pool["pk"], pids, offs, kq, layer_ixs=lsel
                ),
                pv=paged_kv.scatter_prefill(
                    pool["pv"], pids, offs, vq, layer_ixs=lsel
                ),
                pk_scale=paged_kv.scatter_prefill(
                    pool["pk_scale"], pids, offs, ks, layer_ixs=lsel
                ),
                pv_scale=paged_kv.scatter_prefill(
                    pool["pv_scale"], pids, offs, vs, layer_ixs=lsel
                ),
            )
        else:
            pool = dict(
                pool,
                pk=paged_kv.scatter_prefill(
                    pool["pk"], pids, offs, ck, layer_ixs=lsel
                ),
                pv=paged_kv.scatter_prefill(
                    pool["pv"], pids, offs, cv, layer_ixs=lsel
                ),
            )
        return pool, out["hidden_states"][:, -1]

    def _refill(state: Dict[str, Any]) -> Dict[str, Any]:
        active, qnext = state["active"], state["qnext"]
        order = jnp.argsort(active.astype(jnp.int32), stable=True)
        cand = order[:R]
        navail = jnp.minimum(
            jnp.minimum((~active).sum().astype(jnp.int32), Q - qnext),
            jnp.int32(R),
        )
        if spec.paged:
            navail = jnp.minimum(navail, state["ntop"] // PP)
        do = jnp.arange(R, dtype=jnp.int32) < navail
        slot = jnp.where(do, cand, SLOTS)  # OOB -> scatter drops
        qrow = jnp.where(do, qnext + jnp.arange(R, dtype=jnp.int32), Q)
        qc = jnp.clip(qrow, 0, Q - 1)
        ids = q_ids[qc]
        mask = q_mask[qc]

        ready_r = None
        ready_pg = None
        if serving:
            ready_r = jnp.where(do, q_ready[qc], 0)
            ready_pg = ready_r // PS
        if spec.paged:
            # return the refilled slots' old pages, then allocate fresh
            # prompt pages (often the very pages just freed)
            old = state["table"][jnp.clip(slot, 0, SLOTS - 1)]
            state = _free_slot_pages(
                state, old.reshape(-1), jnp.repeat(do, MP)
            )
            free, ntop = state["free"], state["ntop"]
            table = state["table"].at[slot].set(0, mode="drop")
            pgrid_pp = jnp.arange(PP, dtype=jnp.int32)[None, :]
            if serving:
                # pop fresh pages only for the NON-shared prompt part;
                # the shared prefix maps the cache entry's pages
                want = do[:, None] & (pgrid_pp >= ready_pg[:, None])
                got, free, ntop = paged_kv.pop_pages(
                    free, ntop, want.reshape(-1)
                )
                shared_rows = warm["row_table"][qc][:, :PP]
                entries = jnp.where(
                    pgrid_pp < ready_pg[:, None], shared_rows,
                    got.reshape(R, PP),
                )
            else:
                got, free, ntop = paged_kv.pop_pages(
                    free, ntop, jnp.repeat(do, PP)
                )
                entries = got.reshape(R, PP)
            table = table.at[slot[:, None], pgrid_pp].set(
                entries, mode="drop"
            )
            state = _note_alloc(
                dict(state, free=free, ntop=ntop, table=table), got
            )

        posns = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
        pool, h_last = _prefill_into_slots(
            params, state["pool"], state, ids, mask, posns, slot, do,
            ready=ready_r,
        )
        state = dict(state, pool=pool)
        if spec.spec_decode:
            dpool, _ = _prefill_into_slots(
                draft_params, _draft_pool(state), state, ids, mask, posns,
                slot, do, branch_only=bool(shared),
            )
            state = _with_draft_pool(state, dpool)

        if spec.paged:
            # prompt-pad page COMPACTION: a prompt page holding nothing
            # but pad KV (every position in it has mask 0, so its kmask
            # bit is 0 forever) is dead weight parked on the lane from
            # refill to finish. Release such pages right after prefill:
            # reads of those positions gather the null page under a
            # zero key mask, and neither prefill (done) nor decode
            # (writes only at >= P) ever touches them again. This
            # lowers the engine's HBM floor on ragged prompt mixes —
            # the pool only has to hold REAL tokens plus page-rounding,
            # not the pad overhang of the widest prompt. Detection is
            # per-page over the mask (covers the leading left-pad block
            # AND the serving tier's internal pad gap between a shared
            # prefix and the divergent suffix); shared-prefix entries
            # (< ready_pg) are never candidates — their pages belong to
            # the cache, not this lane.
            mask_pp = jnp.concatenate(
                [mask, jnp.zeros((R, PP * PS - P), jnp.int32)], axis=1
            ) if PP * PS != P else mask
            page_has_real = mask_pp.reshape(R, PP, PS).sum(axis=2) > 0
            pgrid = jnp.arange(PP, dtype=jnp.int32)[None, :]
            is_dead = ~page_has_real & do[:, None]  # [R, PP]
            if serving:
                is_dead = is_dead & (pgrid >= ready_pg[:, None])
            rows_tbl = state["table"][jnp.clip(slot, 0, SLOTS - 1)][:, :PP]
            # the freed pages are this refill's own fresh pops (never a
            # cache entry's), so the release is exact: refcount-free
            # push, or both stream holds dropped in spec-decode mode
            state = _free_slot_pages(
                state, rows_tbl.reshape(-1),
                (is_dead & (rows_tbl > 0)).reshape(-1),
            )
            reclaimed_now = (is_dead & (rows_tbl > 0)).sum().astype(jnp.int32)
            table = state["table"].at[slot[:, None], pgrid].set(
                jnp.where(is_dead, 0, rows_tbl), mode="drop"
            )
            state = dict(
                state, table=table,
                reclaimed=state["reclaimed"] + reclaimed_now,
            )

        logits0 = logit_projection(params)(h_last)
        keys0 = lane_keys(rng, _rng_ids(qc) * N)
        tok0 = sample_token_lanes(keys0, logits0, settings)
        bud = row_budget[qc]
        eos0 = tok0 == eos
        fin0 = eos0 | (bud <= 1)

        def upd(name, val):
            return state[name].at[slot].set(val, mode="drop")

        npad = P - mask.sum(axis=1).astype(jnp.int32)
        state = dict(
            state,
            pos=upd("pos", jnp.full((R,), P, jnp.int32)),
            npad=upd("npad", npad),
            new=upd("new", jnp.ones((R,), jnp.int32)),
            budget=upd("budget", bud),
            active=upd("active", ~fin0),
            pidx=upd("pidx", qc),
            cur=upd("cur", tok0),
            kmask=state["kmask"].at[slot].set(
                jnp.concatenate(
                    [mask, jnp.zeros((R, S - P), jnp.int32)], axis=1
                ),
                mode="drop",
            ),
            resp_ids=state["resp_ids"].at[qrow, 0].set(tok0, mode="drop"),
            resp_mask=state["resp_mask"].at[qrow, 0].set(1, mode="drop"),
            qnext=qnext + navail,
            refills=state["refills"] + navail,
            emitted=state["emitted"] + navail,
            truncated=state["truncated"]
            + (do & fin0 & ~eos0).sum().astype(jnp.int32),
        )
        # lanes that finish AT refill (instant EOS / budget 1) must
        # release their freshly-allocated pages immediately, or a fully
        # EOS-degenerate policy parks every page on idle lanes and the
        # refill gate (ntop >= PP) wedges the queue closed
        fin_lanes = (
            jnp.zeros((SLOTS,), bool).at[slot].set(do & fin0, mode="drop")
        )
        return _release_pages(state, fin_lanes)

    def _release_pages(state: Dict[str, Any], lanes: Array) -> Dict[str, Any]:
        """Return `lanes`' pages to the free stack the moment the lane
        finishes: a finished response's KV is dead, and reclaiming it
        immediately is what lets the refill gate (`ntop >= PP`) admit
        the next prompt without a separate scavenging pass.

        Serving mode: a PINNED lane (multi-turn session / a request
        adopted into the prefix cache) keeps every page — its final
        table row and KV length are saved for the host to adopt, and
        its page count lands in the ``pinned_pages`` stat (a pin is a
        normal finish: deliberately NOT counted as reclaimed or
        truncated). Unpinned lanes release through the refcounted path,
        so a shared prefix page only ever decrements down to the
        cache's own hold."""
        if not spec.paged:
            return state
        rows = state["table"]
        if serving:
            pidx = jnp.clip(state["pidx"], 0, Q - 1)
            pin = q_pin[pidx] & lanes
            wrow = jnp.where(pin, state["pidx"], Q)
            saved_tables = state["saved_tables"].at[wrow].set(
                rows, mode="drop"
            )
            saved_len = state["saved_len"].at[wrow].set(
                state["pos"], mode="drop"
            )
            pinned = state["pinned"] + (
                (rows > 0) & pin[:, None]
            ).sum().astype(jnp.int32)
            release = lanes & ~pin
            free, ntop, refcnt = paged_kv.release_refcounted(
                state["free"], state["ntop"], state["refcnt"],
                rows.reshape(-1), jnp.repeat(release, MP),
            )
            return dict(
                state, free=free, ntop=ntop, refcnt=refcnt,
                table=jnp.where(lanes[:, None], 0, rows),
                saved_tables=saved_tables, saved_len=saved_len,
                pinned=pinned,
            )
        state = _free_slot_pages(
            state, rows.reshape(-1), jnp.repeat(lanes, MP)
        )
        return dict(state, table=jnp.where(lanes[:, None], 0, rows))

    def _ensure_page(state: Dict[str, Any], position: Array) -> Dict[str, Any]:
        """Lazy response-page allocation for each active lane's write at
        `position` [SLOTS]; lanes the pool cannot serve are force-
        finished (counted as oom_truncated)."""
        if not spec.paged:
            return state
        active = state["active"]
        pi = jnp.clip(position // PS, 0, MP - 1)
        have = jnp.take_along_axis(state["table"], pi[:, None], axis=1)[:, 0]
        miss = active & (have == 0)
        got, free, ntop = paged_kv.pop_pages(state["free"], state["ntop"], miss)
        table = state["table"].at[
            jnp.arange(SLOTS), pi
        ].set(jnp.where(miss & (got > 0), got, have))
        starve = miss & (got == 0)
        state = _note_alloc(
            dict(
                state,
                free=free,
                ntop=ntop,
                table=table,
                active=active & ~starve,
                oom=state["oom"] + starve.sum().astype(jnp.int32),
                truncated=state["truncated"] + starve.sum().astype(jnp.int32),
            ),
            got,
        )
        return _release_pages(state, starve)

    def _decode_step(state: Dict[str, Any]) -> Dict[str, Any]:
        state = _ensure_page(state, state["pos"])
        active = state["active"]
        p = jnp.clip(state["pos"], 0, S - 1)
        km = state["kmask"].at[jnp.arange(SLOTS), p].max(active.astype(jnp.int32))
        cache = _paged_cache(state["pool"], dict(state, active=active), p, km)
        out = model(
            params,
            state["cur"][:, None],
            positions=jnp.maximum(p - state["npad"], 0)[:, None],
            cache=cache,
        )
        pool = {
            k: out["cache"][k]
            for k in ("pk", "pv", "pk_scale", "pv_scale")
            if k in out["cache"]
        }
        j = jnp.clip(state["new"], 0, N - 1)
        keys = lane_keys(rng, _rng_ids(state["pidx"]) * N + j)
        tok = sample_token_lanes(keys, out["logits"][:, -1], settings)
        eos_hit = tok == eos
        budget_hit = state["new"] + 1 >= state["budget"]
        fin = eos_hit | budget_hit
        wrow = jnp.where(active, state["pidx"], Q)
        na = active.sum().astype(jnp.int32)
        state = dict(
            state,
            pool=pool,
            kmask=km,
            resp_ids=state["resp_ids"].at[wrow, j].set(tok, mode="drop"),
            resp_mask=state["resp_mask"].at[wrow, j].set(1, mode="drop"),
            pos=state["pos"] + active,
            new=state["new"] + active,
            cur=jnp.where(active, tok, state["cur"]),
            active=active & ~fin,
            decode_steps=state["decode_steps"] + 1,
            lane_steps=state["lane_steps"] + na,
            emitted=state["emitted"] + na,
            truncated=state["truncated"]
            + (active & budget_hit & ~eos_hit).sum().astype(jnp.int32),
        )
        return _release_pages(state, active & fin)

    def _spec_round(state: Dict[str, Any]) -> Dict[str, Any]:
        # pages for the whole draft window [pos, pos+K)
        for j in range(K):
            state = _ensure_page(state, state["pos"] + j)
        active = state["active"]
        p = jnp.clip(state["pos"], 0, S - K)
        window = p[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
        km = state["kmask"].at[
            jnp.arange(SLOTS)[:, None], window
        ].max(jnp.broadcast_to(active.astype(jnp.int32)[:, None], (SLOTS, K)))
        base_pos = jnp.maximum(p - state["npad"], 0)

        # -- draft: K single-token steps off the reference ---------------
        def dbody(carry, j):
            dpool, tok_in = carry
            cache = _paged_cache(
                dpool, dict(state, active=active), p + j, km, draft=True
            )
            out = model(
                draft_params, tok_in[:, None],
                positions=(base_pos + j)[:, None], cache=cache,
            )
            dpool = {
                k: out["cache"][k]
                for k in ("pk", "pv", "pk_scale", "pv_scale")
                if k in out["cache"]
            }
            ql = process_logits(out["logits"][:, -1], settings)
            keys = lane_keys(
                rng, _rng_ids(state["pidx"]) * N + state["new"] + j
            )
            if settings.do_sample:
                g = jax.vmap(lambda k2: jax.random.gumbel(k2, (ql.shape[-1],)))(
                    keys
                )
                x = jnp.argmax(ql + g, axis=-1).astype(jnp.int32)
            else:
                x = jnp.argmax(ql, axis=-1).astype(jnp.int32)
            return (dpool, x), (x, jax.nn.softmax(ql, axis=-1))

        (dpool, _), (xs, qprobs) = jax.lax.scan(
            dbody, (_draft_pool(state), state["cur"]),
            jnp.arange(K, dtype=jnp.int32),
        )
        xs = xs.transpose(1, 0)  # [SLOTS, K]

        # -- verify: ONE policy forward over the k drafted inputs --------
        # Trunk sharing: the verify runs on the POST-draft pool (the
        # draft just wrote its branch KV into the extension layers —
        # and its trunk writes, which the verify's own update-carry-
        # first scatter overwrites with the identical values).
        ver_in = jnp.concatenate([state["cur"][:, None], xs[:, : K - 1]], axis=1)
        ver_pool = dpool if shared else state["pool"]
        cache = _paged_cache(ver_pool, dict(state, active=active), p, km)
        out = model(
            params, ver_in,
            positions=base_pos[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :],
            cache=cache,
        )
        pool = {
            k: out["cache"][k]
            for k in ("pk", "pv", "pk_scale", "pv_scale")
            if k in out["cache"]
        }
        pl_ = process_logits(out["logits"], settings)  # [SLOTS, K, V]
        pprobs = jax.nn.softmax(pl_, axis=-1)

        # -- rejection sampling (exact: accepted + residual draws leave
        # the marginal of every emitted token the POLICY's) -------------
        still = active
        fin = jnp.zeros((SLOTS,), bool)
        m = jnp.zeros((SLOTS,), jnp.int32)
        last = state["cur"]
        resp_ids, resp_mask = state["resp_ids"], state["resp_mask"]
        truncated = state["truncated"]
        drafted = state["drafted"]
        accepted = state["accepted"]
        emitted = state["emitted"]
        for j in range(K):
            xj = xs[:, j]
            pj = pprobs[:, j]
            qj = qprobs[j]
            if settings.do_sample:
                ukeys = lane_keys(
                    rng,
                    OFF_ACC + _rng_ids(state["pidx"]) * N + state["new"] + j,
                )
                u = jax.vmap(lambda k2: jax.random.uniform(k2, ()))(ukeys)
                px = jnp.take_along_axis(pj, xj[:, None], axis=1)[:, 0]
                qx = jnp.take_along_axis(qj, xj[:, None], axis=1)[:, 0]
                acc = u * qx <= px
                res = jnp.maximum(pj - qj, 0.0)
                rs = res.sum(axis=-1, keepdims=True)
                res = jnp.where(rs > 1e-12, res / jnp.maximum(rs, 1e-30), pj)
                rkeys = lane_keys(
                    rng,
                    OFF_RES + _rng_ids(state["pidx"]) * N + state["new"] + j,
                )
                tok_rej = categorical_lanes(rkeys, res)
            else:
                am = jnp.argmax(pj, axis=-1).astype(jnp.int32)
                acc = xj == am
                tok_rej = am
            tok = jnp.where(acc, xj, tok_rej)
            emit = still
            wrow = jnp.where(emit, state["pidx"], Q)
            wcol = jnp.clip(state["new"] + j, 0, N - 1)
            resp_ids = resp_ids.at[wrow, wcol].set(tok, mode="drop")
            resp_mask = resp_mask.at[wrow, wcol].set(1, mode="drop")
            eos_hit = tok == eos
            budget_hit = state["new"] + j + 1 >= state["budget"]
            fin_now = emit & (eos_hit | budget_hit)
            m = m + emit
            last = jnp.where(emit, tok, last)
            truncated = truncated + (fin_now & ~eos_hit).sum().astype(jnp.int32)
            drafted = drafted + emit.sum().astype(jnp.int32)
            accepted = accepted + (emit & acc).sum().astype(jnp.int32)
            emitted = emitted + emit.sum().astype(jnp.int32)
            still = still & acc & ~fin_now
            fin = fin | fin_now

        # kmask in the draft window becomes "consumed inputs only": the
        # first m positions stay attendable (their KV is final), stale
        # slots from rejected/over-budget drafts are cleared. Window
        # positions all sit at >= pos, so inactive lanes' real bits are
        # untouched (their window bits were never set).
        keep = (
            jnp.arange(K, dtype=jnp.int32)[None, :] < m[:, None]
        ) & active[:, None]
        km = km.at[jnp.arange(SLOTS)[:, None], window].set(
            keep.astype(jnp.int32)
        )
        # shared mode: `pool` (the verify output) already carries the
        # draft's branch-layer writes — there is no second buffer
        state = dict(
            state,
            pool=pool,
            **({} if shared else {"dpool": dpool}),
            kmask=km,
            resp_ids=resp_ids,
            resp_mask=resp_mask,
            pos=state["pos"] + m,
            new=state["new"] + m,
            cur=last,
            active=active & ~fin,
            decode_steps=state["decode_steps"] + K + 1,
            lane_steps=state["lane_steps"] + (K + 1) * active.sum().astype(jnp.int32),
            rounds=state["rounds"] + 1,
            emitted=emitted,
            truncated=truncated,
            drafted=drafted,
            accepted=accepted,
        )
        return _release_pages(state, active & fin)

    step_fn = _spec_round if spec.spec_decode else _decode_step

    def cond(state):
        can_refill = (~state["active"]).any() & (state["qnext"] < Q)
        if spec.paged:
            can_refill = can_refill & (state["ntop"] >= PP)
        return state["active"].any() | can_refill

    def body(state):
        need = (~state["active"]).any() & (state["qnext"] < Q)
        if spec.paged:
            need = need & (state["ntop"] >= PP)
        state = jax.lax.cond(need, _refill, lambda s: s, state)
        state = jax.lax.cond(
            state["active"].any(), step_fn, lambda s: s, state
        )
        return state

    final = jax.lax.while_loop(cond, body, _init_state())

    resp_ids = jnp.where(final["resp_mask"] > 0, final["resp_ids"], pad)
    steps_f = jnp.maximum(final["decode_steps"].astype(jnp.float32), 1.0)
    stats = {
        "decode_steps": final["decode_steps"],
        "refills": final["refills"],
        "real_tokens": final["emitted"],
        "occupancy": final["lane_steps"].astype(jnp.float32)
        / (steps_f * SLOTS),
        "truncated": final["truncated"],
        "oom_truncated": final["oom"],
        "reclaimed_pages": final["reclaimed"],
        "unserved": Q - final["qnext"],
    }
    if spec.paged:
        # end-of-call free-stack depth: with every lane finished this
        # must equal pool - 1 (the null page) — the `free + held ==
        # pool` balance the spec-decode accounting tests pin
        stats["free_pages"] = final["ntop"]
    if refcounted:
        # pages still refcount-held at exit (0 after a drained chunk):
        # free_pages + held_pages + 1 null page == pool, always
        stats["held_pages"] = (final["refcnt"] > 0).sum().astype(jnp.int32)
    if spec.spec_decode:
        stats.update(
            spec_rounds=final["rounds"],
            drafted=final["drafted"],
            accepted=final["accepted"],
        )
    out = {
        "sequences": jnp.concatenate([q_ids, resp_ids], axis=1),
        "response_ids": resp_ids,
        "response_mask": final["resp_mask"],
        "gen_stats": stats,
    }
    if serving:
        stats["pinned_pages"] = final["pinned"]
        # the persistent pool state the serving host carries into the
        # next call (plus per-row pin adoptions)
        out["kv_state"] = {
            "pool": final["pool"],
            "free": final["free"],
            "ntop": final["ntop"],
            "refcnt": final["refcnt"],
            "saved_tables": final["saved_tables"],
            "saved_len": final["saved_len"],
        }
    return out


def engine_generate_grouped(
    model: TransformerLM,
    params: Dict,
    q_ids: Array,  # [Q, P]
    q_mask: Array,  # [Q, P]
    rng: jax.Array,
    settings: SamplerSettings,
    spec: EngineSpec,
    draft_params: Optional[Dict] = None,
    row_budget: Optional[Array] = None,
    group_sharding=None,
) -> Dict[str, Array]:
    """Run the engine as ``spec.data_groups`` INDEPENDENT lane groups.

    The queue splits into G contiguous shards; each shard gets its own
    full engine instance — slots, page pool, page table, free stack —
    and all G run as ONE stacked dispatch (`jax.vmap` over the group
    axis). With ``group_sharding`` (a `NamedSharding` whose axis 0 spec
    names mesh data axes) the stacked queue is sharding-constrained so
    GSPMD places each group's engine state — pools, tables, slot lanes
    — on that group's device slice: the engine's control flow stays one
    program, but its memory and per-step compute shard over the mesh
    instead of replicating (multi-chip rollout workers / serve
    frontends, ROADMAP item 3's second half).

    Output equivalence is structural: RNG ids are the GLOBAL queue row
    (``q_rng_row``) and the acceptance/residual offsets use the global
    id space (``rng_space``), so greedy output is token-for-token the
    single-group engine's, and sampled streams are the same draws. A
    queue not divisible by G is padded with dummy rows (one real token,
    budget 1 — the serving tier's padding trick); their emissions are
    trimmed from the outputs and subtracted from the stats.
    """
    G = spec.data_groups
    if G <= 1:
        return engine_generate(
            model, params, q_ids, q_mask, rng, settings, spec,
            draft_params=draft_params, row_budget=row_budget,
        )
    Q, P = q_ids.shape
    N = settings.max_new_tokens
    Qg = -(-Q // G)
    npad = G * Qg - Q
    q_ids = q_ids.astype(jnp.int32)
    q_mask = q_mask.astype(jnp.int32)
    if row_budget is None:
        row_budget = jnp.full((Q,), N, jnp.int32)
    row_budget = jnp.clip(row_budget.astype(jnp.int32), 1, N)
    if npad:
        pad_ids = jnp.full(
            (npad, P), settings.pad_token_id, jnp.int32
        ).at[:, -1].set(0)
        pad_mask = jnp.zeros((npad, P), jnp.int32).at[:, -1].set(1)
        q_ids = jnp.concatenate([q_ids, pad_ids])
        q_mask = jnp.concatenate([q_mask, pad_mask])
        row_budget = jnp.concatenate(
            [row_budget, jnp.ones((npad,), jnp.int32)]
        )
    rng_rows = jnp.arange(G * Qg, dtype=jnp.int32)

    def split(x):
        return x.reshape((G, Qg) + x.shape[1:])

    gq_ids, gq_mask = split(q_ids), split(q_mask)
    g_budget, g_rows = split(row_budget), split(rng_rows)
    if group_sharding is not None:
        gq_ids = jax.lax.with_sharding_constraint(gq_ids, group_sharding)
        gq_mask = jax.lax.with_sharding_constraint(gq_mask, group_sharding)
    # an EXPLICIT pool_pages is the TOTAL page budget (same meaning as
    # the single-group run): each group gets its ceil(1/G) share. Note
    # the one caveat this implies: under a DELIBERATELY undersized
    # budget, which lanes oom-truncate can differ from the single-group
    # run (allocation is per-group, not global) — the token-for-token
    # guarantee is for pools that don't starve, and the default
    # worst-case sizing (pool_pages=0) never starves.
    sub = dataclasses.replace(
        spec, data_groups=1,
        pool_pages=-(-spec.pool_pages // G) if spec.pool_pages else 0,
    )
    SLOTS = max(1, min(sub.slots, Qg))

    def one_group(ids, mask, budget, rows):
        return engine_generate(
            model, params, ids, mask, rng, settings, sub,
            draft_params=draft_params, row_budget=budget,
            q_rng_row=rows, rng_space=Q,
        )

    out = jax.vmap(one_group)(gq_ids, gq_mask, g_budget, g_rows)
    merged = {
        k: out[k].reshape((G * Qg,) + out[k].shape[2:])[:Q]
        for k in ("sequences", "response_ids", "response_mask")
    }
    g = out["gen_stats"]  # every stat is [G]
    steps = g["decode_steps"].sum()
    lane_steps = g["occupancy"] * g["decode_steps"].astype(jnp.float32) * SLOTS
    stats: Dict[str, Array] = {
        "decode_steps": steps,
        "refills": g["refills"].sum(),
        "real_tokens": g["real_tokens"].sum(),
        "occupancy": lane_steps.sum()
        / jnp.maximum(steps.astype(jnp.float32) * SLOTS, 1.0),
        "truncated": g["truncated"].sum(),
        "oom_truncated": g["oom_truncated"].sum(),
        "reclaimed_pages": g["reclaimed_pages"].sum(),
        "unserved": g["unserved"].sum(),
    }
    for k in ("free_pages", "held_pages", "spec_rounds", "drafted", "accepted"):
        if k in g:
            stats[k] = g[k].sum()
    if npad:
        # dummy-row corrections: each pad row emits exactly its single
        # budgeted token through one refill, and counts truncated
        # unless that token happened to be EOS
        dummy_tok = out["response_ids"].reshape(G * Qg, -1)[Q:, 0]
        dummy_eos = (dummy_tok == jnp.int32(settings.eos_token_id)).sum(
            dtype=jnp.int32
        )
        stats["real_tokens"] = stats["real_tokens"] - npad
        stats["refills"] = stats["refills"] - npad
        stats["truncated"] = stats["truncated"] - (npad - dummy_eos)
    merged["gen_stats"] = stats
    return merged


def make_engine_fn(
    model: TransformerLM,
    settings: SamplerSettings,
    spec: EngineSpec,
):
    """Jitted engine entry: `(params[, draft_params], q_ids, q_mask,
    rng[, row_budget]) -> outputs`. One executable per (Q, P) shape.
    Routes through the grouped wrapper when the spec asks for sharded
    lane groups (`data_groups > 1`)."""
    run = (
        engine_generate_grouped if spec.data_groups > 1 else engine_generate
    )
    if spec.spec_decode:

        @jax.jit
        def engine_generate_spec(
            params, draft_params, q_ids, q_mask, rng, row_budget=None
        ):
            return run(
                model, params, q_ids, q_mask, rng, settings, spec,
                draft_params=draft_params, row_budget=row_budget,
            )

        return engine_generate_spec

    def fn(params, q_ids, q_mask, rng, row_budget=None):
        return run(
            model, params, q_ids, q_mask, rng, settings, spec,
            row_budget=row_budget,
        )

    fn.__name__ = "engine_generate"  # the XLA module is jit_engine_generate
    return jax.jit(fn)
