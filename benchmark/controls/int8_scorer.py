"""The control behind a configuration's `correct` limits: the cell's scorer with
its weights in the nearest precision below the one the configuration states has
to come out NOT correct, through the harness's own comparison.

    python3 benchmark/controls/int8_scorer.py --workload <cell> --seed <n> [--rehearse]

sets the cell up as `run.py` does (same files, same seed, same first rollouts)
and, where the warm-up makes its reference check, makes it twice: on the
log-probabilities the scorer gave, and on those the same compiled scorer gives
over the same tokens once every weight the rollout's int8 rewrite covers
(`quantize_decode_weights`) is rounded to int8 and back. Both go through
`correct.reference_check` / `correct.scorer_check` with the configuration's
limits; the reference is the one the first check computed. The last line is
`INT8_CONTROL {...}`; the exit code is 0 when the first is correct and the
second is not, 1 otherwise. Nothing is measured and no window opens. On the
CPU (`--rehearse`, toy sizes) the numbers say nothing about the limits: it
rehearses the code."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import correct, run  # noqa: E402


def rounded_to_int8(base):
    """`base` with every kernel `quantize_decode_weights` rewrites replaced by
    int8 x scale in float32: the same tree, so the compiled scorer takes it."""
    import jax.numpy as jnp

    from trlx_tpu.models.transformer import quantize_decode_weights

    def walk(tree, name=""):
        if not isinstance(tree, dict):
            return tree
        new = {k: walk(v, k) for k, v in tree.items() if k != "kernel_scale"}
        if "kernel_scale" in tree:
            w, s = tree["kernel"].astype(jnp.float32), tree["kernel_scale"]
            # the scale lacks the kernel's input dimensions: behind the layer
            # axis, and for stacked expert kernels behind the expert axis too
            first = 2 if name.startswith("experts_") else 1
            new["kernel"] = w * jnp.expand_dims(s, tuple(range(first, first + w.ndim - s.ndim)))
        return new

    return walk(quantize_decode_weights(base))


def control(check):
    def both(trainer, cell, hf, rows):
        import jax
        import jax.numpy as jnp
        import numpy as np

        seen = {}
        compare = correct.scorer_check

        def spy(system, reference, decisive, tol):
            seen.update(reference=reference, decisive=decisive)
            return compare(system, reference, decisive, tol)

        correct.scorer_check = spy
        try:
            sound = check(trainer, cell, hf, rows)
        finally:
            correct.scorer_check = compare

        hist = trainer.store.history
        pad = trainer.generate_settings.pad_token_id
        resp_mask = jnp.asarray(np.asarray(hist.response_mask) > 0, jnp.int32)
        tokens = jnp.concatenate([hist.query_tensors, hist.response_tensors], axis=1).astype(jnp.int32)
        mask = jnp.concatenate([(hist.query_tensors != pad).astype(jnp.int32), resp_mask], axis=1)
        P, N = hist.query_tensors.shape[1], hist.response_tensors.shape[1]
        scorer = trainer._get_experience_fwd_fn(P, N)
        args = (tokens, mask, resp_mask, jnp.float32(trainer.kl_ctl.value),
                jnp.ones((tokens.shape[0],), jnp.float32))
        keep = np.asarray(resp_mask[:rows]) > 0
        with trainer.mesh:
            again = np.asarray(scorer(trainer.params, trainer.ref_params, *args)[0].logprobs[:rows])[keep]
            low = dict(trainer.params, base=jax.jit(rounded_to_int8, donate_argnums=0)(trainer.params["base"]))
            int8 = np.asarray(scorer(low, trainer.ref_params, *args)[0].logprobs[:rows])[keep]
        stored = np.asarray(hist.logprobs[:rows])[keep]
        out = {
            "cell": cell.name,
            "positions": int(keep.sum()),
            # the scorer called here reproduces what the rollouts stored
            "scorer_again_max_abs": float(np.max(np.abs(again - stored))),
            "limits": {k: v for k, v in cell.config["correct"].items() if k != "why"},
            "as_run": {k: v for k, v in sound.items() if k.startswith(("logprob", "tie", "decisive", "scorer"))},
            "int8": compare(int8, seen["reference"], seen["decisive"], cell.config["correct"]),
        }
        ok = out["as_run"]["scorer_ok"] and not out["int8"]["scorer_ok"]
        print("INT8_CONTROL " + json.dumps(out), flush=True)
        sys.stdout.flush()
        os._exit(0 if ok else 1)

    return both


if __name__ == "__main__":
    correct.reference_check = control(correct.reference_check)
    sys.argv += ["--seconds", "1", "--trace", "0"]
    sys.exit(run.main())
