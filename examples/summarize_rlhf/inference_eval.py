"""Stage 4: evaluate a trained TL;DR policy — ROUGE-1/2/L vs the human
summaries plus reward-model score over a test split.

Parity: /root/reference/examples/summarize_rlhf/trlx_inference_gptj.py
(generation + ROUGE table) and reward_model/gptj_reward_test.py (RM
score over the test set). Together with the README table these scripts
produce the reference's only published-metric baseline (BASELINE.md:
ROUGE-1/2/L/avg 0.334/0.125/0.261/0.240 for SFT, mean reward 2.729 SFT
-> 3.291 PPO), so this script emits the same schema.

ROUGE here is first-party (`rouge_scores` below: unigram/bigram F1 and
LCS F1 over whitespace-ish tokens, the same definition `evaluate`'s
default rouge uses) so the eval runs with zero network egress; if the
`evaluate` package has a cached rouge it is preferred.

Air-gapped smoke path: `SMOKE=1 python inference_eval.py` runs the full
mechanics (generation -> ROUGE -> table) on a tiny random-init model
with the byte tokenizer and synthetic posts — no checkpoints, no
network — exercising every line except real checkpoint loading.
"""

import json
import os
import re
import sys
from collections import Counter
from typing import Dict, List, Optional


# ---------------------------------------------------------------------------
# first-party ROUGE (zero-egress replacement for evaluate.load("rouge"))
# ---------------------------------------------------------------------------


def _tokens(text: str) -> List[str]:
    return re.findall(r"[a-z0-9]+", text.lower())


def _f1(match: int, pred: int, ref: int) -> float:
    if pred == 0 or ref == 0 or match == 0:
        return 0.0
    p, r = match / pred, match / ref
    return 2 * p * r / (p + r)


def _ngram_f1(pred: List[str], ref: List[str], n: int) -> float:
    pg = Counter(zip(*[pred[i:] for i in range(n)]))
    rg = Counter(zip(*[ref[i:] for i in range(n)]))
    match = sum((pg & rg).values())
    return _f1(match, max(sum(pg.values()), 0), max(sum(rg.values()), 0))


def _lcs_len(a: List[str], b: List[str]) -> int:
    if not a or not b:
        return 0
    prev = [0] * (len(b) + 1)
    for x in a:
        cur = [0]
        for j, y in enumerate(b, 1):
            cur.append(prev[j - 1] + 1 if x == y else max(prev[j], cur[-1]))
        prev = cur
    return prev[-1]


def rouge_scores(predictions: List[str], references: List[str]) -> Dict[str, float]:
    """Corpus-mean ROUGE-1/2/L F-measures."""
    r1 = r2 = rl = 0.0
    for pred_text, ref_text in zip(predictions, references):
        pred, ref = _tokens(pred_text), _tokens(ref_text)
        r1 += _ngram_f1(pred, ref, 1)
        r2 += _ngram_f1(pred, ref, 2)
        rl += _f1(_lcs_len(pred, ref), len(pred), len(ref))
    n = max(len(predictions), 1)
    return {"rouge1": r1 / n, "rouge2": r2 / n, "rougeL": rl / n}


def compute_rouge(predictions: List[str], references: List[str]) -> Dict[str, float]:
    try:  # prefer a locally cached `evaluate` rouge when present
        import evaluate

        r = evaluate.load("rouge").compute(
            predictions=predictions, references=references
        )
        return {k: float(r[k]) for k in ("rouge1", "rouge2", "rougeL")}
    except Exception:
        return rouge_scores(predictions, references)


# ---------------------------------------------------------------------------
# generation
# ---------------------------------------------------------------------------


def generate_summaries(
    lm, params, tokenizer, posts: List[str], max_prompt: int, max_new: int,
    batch_size: int = 16,
) -> List[str]:
    """Left-padded batched sampling of `max_new` tokens per post."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.generation import SamplerSettings, make_generate_fn

    settings = SamplerSettings(
        max_new_tokens=max_new,
        do_sample=False,
        eos_token_id=tokenizer.eos_token_id if tokenizer.eos_token_id is not None else -1,
        pad_token_id=tokenizer.pad_token_id or 0,
    )
    tokenizer.padding_side = "left"
    fn = make_generate_fn(lm, settings)
    rng = jax.random.PRNGKey(0)
    preds = []
    for i in range(0, len(posts), batch_size):
        chunk = posts[i : i + batch_size]
        pad_to = batch_size  # one compiled sampler for every chunk
        chunk = chunk + [chunk[-1]] * (pad_to - len(chunk))
        enc = tokenizer(
            chunk, truncation=True, padding="max_length", max_length=max_prompt
        )
        rng, sub = jax.random.split(rng)
        out = fn(
            params,
            jnp.asarray(enc["input_ids"], jnp.int32),
            jnp.asarray(enc["attention_mask"], jnp.int32),
            sub,
        )
        texts = tokenizer.batch_decode(
            [[t for t, m in zip(ids, mask) if m] for ids, mask in zip(
                out["response_ids"].tolist(), out["response_mask"].tolist()
            )]
        )
        preds.extend(texts[: len(posts[i : i + batch_size])])
    return preds


# ---------------------------------------------------------------------------
# table (BASELINE.md schema)
# ---------------------------------------------------------------------------


def emit_table(name: str, rouge: Dict[str, float], mean_reward: Optional[float]):
    avg = (rouge["rouge1"] + rouge["rouge2"] + rouge["rougeL"]) / 3
    print(f"| TL;DR ROUGE-1 / ROUGE-2 / ROUGE-L / avg ({name}) | "
          f"{rouge['rouge1']:.3f} / {rouge['rouge2']:.3f} / "
          f"{rouge['rougeL']:.3f} / {avg:.3f} |")
    if mean_reward is not None:
        print(f"| TL;DR summarization, mean reward ({name}) | {mean_reward:.3f} |")
    print(json.dumps({"model": name, **{k: round(v, 4) for k, v in rouge.items()},
                      "rouge_avg": round(avg, 4),
                      "mean_reward": None if mean_reward is None
                      else round(mean_reward, 4)}))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def run_eval(model_dir: str, name: str, n_samples: int = 100):
    """Real path: HF-layout checkpoint + TL;DR test split + optional RM."""
    from datasets import load_dataset

    from trlx_tpu.data.configs import TokenizerConfig
    from trlx_tpu.models.hf import load_pretrained
    from trlx_tpu.utils.tokenizers import load_tokenizer

    lm, params, _ = load_pretrained(model_dir)
    tokenizer = load_tokenizer(TokenizerConfig(tokenizer_path=model_dir,
                                               truncation_side="left"))
    test = load_dataset("CarperAI/openai_summarize_tldr", split="test")
    posts = [x["prompt"] for x in test][:n_samples]
    refs = [x["label"] for x in test][:n_samples]

    preds = generate_summaries(lm, params, tokenizer, posts,
                               max_prompt=500, max_new=50)
    preds = [p.split("TL;DR:")[-1] for p in preds]
    rouge = compute_rouge(preds, refs)

    mean_reward = None
    rm_dir = os.environ.get("RM_DIR")
    if rm_dir:  # RM score of post+summary (gptj_reward_test.py analog)
        from examples.summarize_rlhf.ppo_summarize import make_rm_reward_fn

        rm_score = make_rm_reward_fn(rm_dir)
        scores = rm_score([p + " " + s for p, s in zip(posts, preds)])
        mean_reward = float(scores.mean())
    emit_table(name, rouge, mean_reward)


def run_smoke():
    """Air-gapped mechanics check: tiny random model, byte tokenizer,
    synthetic posts/references. Asserts the table emits and ROUGE is
    self-consistent (predicting the reference scores 1.0)."""
    import jax

    # a mechanics check, not a measurement: force CPU before any
    # backend initializes so it never takes a chip
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from trlx_tpu.models.transformer import TransformerConfig, TransformerLM
    from trlx_tpu.utils.tokenizers import ByteTokenizer

    cfg = TransformerConfig(
        vocab_size=260, hidden_size=32, n_layer=2, n_head=2, n_positions=128,
        dtype=jnp.float32,
    )
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    tokenizer = ByteTokenizer()

    posts = [f"post number {i} about a cat on a mat TL;DR:" for i in range(6)]
    refs = [f"cat {i} sits" for i in range(6)]
    preds = generate_summaries(lm, params, tokenizer, posts,
                               max_prompt=48, max_new=8, batch_size=4)
    assert len(preds) == len(posts)

    # the metric itself: identical strings score 1.0 across the board
    perfect = rouge_scores(refs, refs)
    assert all(abs(v - 1.0) < 1e-9 for v in perfect.values()), perfect
    rouge = compute_rouge(preds, refs)
    emit_table("smoke", rouge, mean_reward=None)
    print("smoke OK")


if __name__ == "__main__":
    if os.environ.get("SMOKE") == "1" or "--smoke" in sys.argv:
        run_smoke()
    else:
        model_dir = sys.argv[1] if len(sys.argv) > 1 else (
            "ckpts/ppo_summarize/best_checkpoint/hf_model"
        )
        name = sys.argv[2] if len(sys.argv) > 2 else "PPO"
        run_eval(model_dir, name, n_samples=int(os.environ.get("N_SAMPLES", "100")))
