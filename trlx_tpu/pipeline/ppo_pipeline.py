"""PPO rollout storage.

Parity: /root/reference/trlx/pipeline/ppo_pipeline.py:14-104. The
reference stores ragged per-sample tensors and pads at collate time;
rollouts here are born rectangular (PPORolloutBatch — queries left-padded
to max_prompt_length, responses right-padded to max_new_tokens), so the
store is row-indexed and collation is pure slicing: zero host compute
between rollout and train step.

Rollouts pushed as jax Arrays STAY ON DEVICE: the experience fn's outputs
are already sharded device arrays, and a device->host round-trip per
array is a sync the rollout loop does not need. Batching then happens
by device-side gather with a host-generated permutation.
"""

from __future__ import annotations

import json
import os
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data import PPORolloutBatch
from trlx_tpu.pipeline import BaseRolloutStore, DataLoader


class _DeviceGatherLoader:
    """Minimal loader over a device-resident rectangular pytree: yields
    `tree[perm[i*b:(i+1)*b]]` device gathers, no host copies.

    Keep the shuffle/drop_last/len semantics in lockstep with
    `pipeline.DataLoader` — the host and device paths must produce the
    same batch composition for a given seed, and the FIRST iteration's
    order must equal `pipeline.epoch_shuffle_order(n, seed)` (the
    scanned-epoch path derives its permutations from it; pinned by
    tests/test_scanned_epochs.py)."""

    def __init__(self, history, batch_size, shuffle, drop_last, seed):
        self.history = history
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self._rng = np.random.default_rng(seed)

    def _n(self) -> int:
        return len(jax.tree_util.tree_leaves(self.history)[0])

    def __len__(self) -> int:
        n = self._n()
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    def __iter__(self):
        n = self._n()
        order = np.arange(n)
        if self.shuffle:
            self._rng.shuffle(order)
        for start in range(0, n, self.batch_size):
            idxs = order[start : start + self.batch_size]
            if self.drop_last and len(idxs) < self.batch_size:
                return
            yield jax.tree_util.tree_map(lambda x: x[idxs], self.history)


class PPORolloutStorage(BaseRolloutStore):
    """Experience buffer of PPO rollouts (pushed as PPORolloutBatch)."""

    def __init__(self, pad_token_id: int = 0):
        super().__init__()
        self.pad_token_id = pad_token_id
        self.history: Optional[PPORolloutBatch] = None

    def push(self, exps: PPORolloutBatch) -> None:
        def _on_device(tree) -> bool:
            return any(
                isinstance(leaf, jax.Array)
                for leaf in jax.tree_util.tree_leaves(tree)
            )

        # residency follows the held history so one mixed push can never
        # silently download the whole device buffer: a device history
        # promotes incoming host batches (cheap upload), a host history
        # demotes incoming device batches
        if self.history is not None:
            on_device = _on_device(self.history)
        else:
            on_device = _on_device(exps)
        if on_device:
            exps = jax.tree_util.tree_map(jnp.asarray, exps)
        else:
            exps = jax.tree_util.tree_map(np.asarray, exps)
        if self.history is None:
            self.history = exps
        else:
            cat = jnp.concatenate if on_device else np.concatenate
            self.history = jax.tree_util.tree_map(
                lambda a, b: cat([a, b], axis=0), self.history, exps
            )

    def clear_history(self) -> None:
        self.history = None

    def __len__(self) -> int:
        return 0 if self.history is None else len(self.history.query_tensors)

    def __getitem__(self, ix: int) -> PPORolloutBatch:
        return jax.tree_util.tree_map(lambda x: x[ix], self.history)

    def export_history(self, location: str, tokenizer=None) -> None:
        """Dump rollouts as JSON for algorithm-distillation-style logging
        (parity: reference ppo_pipeline.py:30-49)."""
        os.makedirs(location, exist_ok=True)
        fpath = os.path.join(location, f"epoch-{str(time.time())}.json")
        history = jax.tree_util.tree_map(np.asarray, self.history)

        def exp_to_dict(i: int):
            # field set varies by batch type (GRPO rollouts carry no
            # values/rewards columns): export what the pytree holds
            d = {
                "query_tensor": history.query_tensors[i].tolist(),
                "response_tensor": history.response_tensors[i].tolist(),
            }
            for fname in ("logprobs", "values", "rewards", "ref_logprobs",
                          "advantages"):
                field = getattr(history, fname, None)
                if field is not None:
                    d[fname] = field[i].tolist()
            if tokenizer is not None:
                d["query"] = tokenizer.decode(d["query_tensor"])
                d["response"] = tokenizer.decode(d["response_tensor"])
            return d

        with open(fpath, "w") as f:
            json.dump([exp_to_dict(i) for i in range(len(self))], f)

    def collate(self, elems: List[PPORolloutBatch]) -> PPORolloutBatch:
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs, axis=0), *elems)

    def fused_epoch_source(self):
        """The whole store as ONE rectangular epoch batch: (pytree,
        n_rows), or None when empty.

        This is the scanned-epoch export: the trainer's fused lax.scan
        gathers minibatch rows from this tree on-device (`tree[perm]`
        inside the scan body), so the ppo_epochs x minibatch loop runs
        without per-step host dispatch. Shuffling stays equivalent to
        the loader path because both draw index orders from
        `pipeline.epoch_shuffle_order`."""
        if self.history is None or len(self) == 0:
            return None
        return self.history, len(self)

    def create_loader(
        self, batch_size: int, shuffle: bool = False, drop_last: bool = False, seed: int = 0
    ):
        if self.history is not None and any(
            isinstance(leaf, jax.Array)
            for leaf in jax.tree_util.tree_leaves(self.history)
        ):
            return _DeviceGatherLoader(
                self.history, batch_size, shuffle, drop_last, seed
            )
        return DataLoader(
            self, batch_size, collate_fn=self.collate, shuffle=shuffle,
            drop_last=drop_last, seed=seed,
        )
