#!/bin/bash
# Single-host training entry (parity: /root/reference/scripts/
# accelerate_train_example.sh — there the launcher was `accelerate
# launch`; SPMD needs no launcher on one host).
#
# One process per chip: run one example at a time on a host's chips.
# trlx_tpu.train() caches compiles under $JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache.
#
# Usage: scripts/train_example.sh examples/ppo_sentiments.py '{"train.total_steps": 100}'
set -euo pipefail
cd "$(dirname "$0")/.."
python "${1:?usage: train_example.sh <script.py> [hparams-json]}" "${2:-{}}"
