#!/bin/bash
# Benchmark matrix (parity: /root/reference/scripts/benchmark.sh — clone
# a branch, run the example matrix, record metrics). Air-gapped subset:
# the randomwalks examples train from scratch; bench.py measures PPO
# throughput on a GPT2-small-class workload.
# One process per chip: each step below is its own process and they run
# one after another. Compiles are cached under $JAX_COMPILATION_CACHE_DIR,
# else <checkout>/.jax_cache (trlx_tpu/utils/compile_cache.py).
set -e
cd "$(dirname "$0")/.."

echo "== randomwalks smoke matrix =="
for script in ppo ilql rft; do
  echo "-- ${script}_randomwalks"
  python - <<PY
import sys; sys.path.insert(0, ".")
from examples.randomwalks.${script}_randomwalks import main
main({"train.total_steps": 40, "train.eval_interval": 20,
      "train.checkpoint_interval": 100000,
      "train.checkpoint_dir": "/tmp/bench_rw_${script}"})
PY
done

echo "== throughput =="
python bench.py
