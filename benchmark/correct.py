"""The comparison that decides `correct`. All of it runs outside the window.

(a) The system's teacher-forced scorer against the configuration's plain
    reference: the log-probability of every sampled token of
    `reference_rows` first rollouts, at the cell's full width, depth and
    sequence length, while the parameters are still the seeded ones.
    Tolerances and their reason are in the configuration's file (`correct`).
    Where the reference makes discrete choices (an expert picked, a block
    selected), its `hidden_states` may return, with the hidden states, a
    boolean per position: true where every choice made on the way to that
    position had a margin above the threshold the file states (the module
    reads it from `hf["correct"]`). The tolerances above then hold on those
    DECISIVE positions; the rest, where bf16 activations may fairly choose
    otherwise, are held to the looser `tie_logprob_rms_tol` and
    `tie_logprob_max_tol`, and the share of decisive positions to
    `decisive_share_min`, all stated in the file. Nothing is skipped.
(b) The sampler against the same reference: tokens drawn from the policy
    have an expected log-probability of minus the entropy, so the mean of
    log p(token) + H over the sampled positions is zero up to sampling
    noise. A sampler that reads a wrong cache slot, position or mask draws
    from another distribution and lands a Kullback-Leibler distance below
    zero (order 1 for unrelated logits). The sampler returns no
    log-probabilities of its own, so this is what can be held against it;
    the int8 rollout policy's own noise (order 1e-3) is below the test.
(c) Every loss of the window finite and below 1e3 (rewards are clipped to
    +-10, which bounds the returns), and as many generated tokens on the
    program's own counter as the traffic mix says.
(d) No program built or loaded, and no cache miss, inside the window.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, List

LOSS_BOUND = 1e3
# (b): the bound is Z standard errors of the sample mean plus SLACK nats
# for the int8 rollout policy's distance from the scorer it is tested
# against. Z = 6 keeps a false alarm under 1e-8 a run.
SAMPLER_Z = 6.0
SAMPLER_SLACK = 0.02


def scorer_check(system, reference, decisive, tol: Dict) -> Dict[str, float]:
    """Check (a) on flat arrays of log-probabilities, the system's and the
    reference's, one entry a sampled position. `decisive` is None (every
    position held to the tolerances) or a boolean array (module doc-string)."""
    import numpy as np

    def errors(diff):
        if diff.size == 0:
            return 0.0, 0.0
        return float(np.sqrt(np.mean(diff**2))), float(np.max(np.abs(diff)))

    diff = np.asarray(system) - np.asarray(reference)
    ok = bool(np.all(np.isfinite(system)))
    if decisive is None:
        rms, worst = errors(diff)
        out = {"logprob_rms_err": rms, "logprob_max_err": worst}
    else:
        decisive = np.asarray(decisive, bool)
        rms, worst = errors(diff[decisive])
        tie_rms, tie_worst = errors(diff[~decisive])
        out = {"logprob_rms_err": rms, "logprob_max_err": worst,
               "tie_logprob_rms_err": tie_rms, "tie_logprob_max_err": tie_worst,
               "decisive_share": float(decisive.mean()) if decisive.size else 0.0}
        ok = (ok and tie_rms <= tol["tie_logprob_rms_tol"] and tie_worst <= tol["tie_logprob_max_tol"]
              and out["decisive_share"] >= tol["decisive_share_min"])
    out["scorer_ok"] = bool(ok and rms <= tol["logprob_rms_tol"] and worst <= tol["logprob_max_tol"])
    return out


def reference_check(trainer, cell, hf: Dict, rows: int) -> Dict[str, float]:
    """Checks (a) and (b) on the rollouts now in the trainer's store."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = cell.reference
    hist = trainer.store.history
    pad = trainer.generate_settings.pad_token_id
    query, response = hist.query_tensors[:rows], hist.response_tensors[:rows]
    resp_mask = np.asarray(hist.response_mask[:rows]) > 0
    tokens = jnp.concatenate([query, response], axis=1).astype(jnp.int32)
    mask = jnp.concatenate(
        [(query != pad).astype(jnp.int32), jnp.asarray(resp_mask, jnp.int32)], axis=1
    )
    P, N = query.shape[1], response.shape[1]

    @jax.jit
    def reference(base, tokens, mask):
        p = ref.params_from_system(base)
        hidden = ref.hidden_states(p, hf, tokens, mask)
        hidden, decisive = hidden if isinstance(hidden, tuple) else (hidden, None)
        hidden = hidden[:, P - 1 : P + N - 1]
        logp = jax.nn.log_softmax(ref.logits(p, hidden), axis=-1)
        taken = jnp.take_along_axis(logp, tokens[:, P:, None], axis=-1)[..., 0]
        entropy = -(jnp.exp(logp) * logp).sum(-1)
        return taken, entropy, None if decisive is None else decisive[:, P - 1 : P + N - 1]

    with trainer.mesh:
        taken, entropy, decisive = reference(trainer.params["base"], tokens, mask)
    taken, entropy = np.asarray(taken)[resp_mask], np.asarray(entropy)[resp_mask]
    if decisive is not None:
        decisive = np.asarray(decisive)[resp_mask]
    system = np.asarray(hist.logprobs[:rows])[resp_mask]
    surprise = taken + entropy
    n = surprise.size
    bound = SAMPLER_Z * float(surprise.std()) / math.sqrt(n) + SAMPLER_SLACK
    out = {
        "positions": n,
        **scorer_check(system, taken, decisive, cell.config["correct"]),
        "sampler_mean_surprise": float(surprise.mean()),
        "sampler_bound": bound,
    }
    out["sampler_ok"] = bool(abs(out["sampler_mean_surprise"]) <= bound)
    return out


def read_stream(run_dir: str) -> List[Dict[str, float]]:
    """The program's own metric stream of this run."""
    with open(os.path.join(run_dir, "logs", "metrics.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def window_check(stream, cycles: List[Dict], traffic: Dict, experiences: int,
                 events: Dict[str, list]) -> Dict:
    """Check (c). A cycle fails when its loss is missing, not finite or
    beyond the bound, or when the program recorded a guardrail trip, a
    memory watermark or a step of its out-of-memory ladder."""
    loss_at = {int(r["_step"]): r["losses/total_loss"] for r in stream
               if "losses/total_loss" in r}
    failed = 0
    for c in cycles:
        loss = loss_at.get(c["step"])
        c["loss"] = loss
        if loss is None or not math.isfinite(loss) or abs(loss) >= LOSS_BOUND:
            failed += 1
    incidents = sum(len(v) for k, v in events.items()
                    if k not in ("run_start", "run_end", "checkpoint"))
    # the stream carries one row per collection: the mean over its chunks
    tokens = sum(r["rollout/real_tokens"] for r in stream if "rollout/real_tokens" in r)
    expected = experiences * traffic["chunk"] * traffic["new_tokens"]
    return {
        "failed": min(len(cycles), failed + incidents),
        "generated_tokens": tokens,
        "expected_tokens": expected,
        "tokens_ok": tokens == expected,
    }


def compile_check(in_window: Dict[str, float]) -> bool:
    """Check (d)."""
    return in_window["compiles"] == 0 and in_window["cache_misses"] == 0


def compared(ref: Dict, win: Dict, in_window: Dict, tol: Dict) -> Dict[str, list]:
    """Every number the checks compared, beside its limit: [number, limit]."""
    out = {"logprob_rms": [ref["logprob_rms_err"], tol["logprob_rms_tol"]],
           "logprob_max": [ref["logprob_max_err"], tol["logprob_max_tol"]]}
    if "decisive_share" in ref:
        out["tie_logprob_rms"] = [ref["tie_logprob_rms_err"], tol["tie_logprob_rms_tol"]]
        out["tie_logprob_max"] = [ref["tie_logprob_max_err"], tol["tie_logprob_max_tol"]]
        out["decisive_share_at_least"] = [ref["decisive_share"], tol["decisive_share_min"]]
    out["sampler_surprise"] = [abs(ref["sampler_mean_surprise"]), ref["sampler_bound"]]
    out["generated_tokens_exactly"] = [win["generated_tokens"], win["expected_tokens"]]
    out["failed_cycles"] = [win["failed"], 0]
    out["built_in_window"] = [in_window["compiles"] + in_window["cache_misses"], 0]
    return out
