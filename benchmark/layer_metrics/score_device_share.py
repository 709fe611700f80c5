"""Percent of the device's busy time spent scoring the experience: the
programs `jit_ppo_experience_fwd` (policy, value head and reference branch,
teacher-forced over prompt and response) and `jit_ppo_score_inject` (rewards
and the KL penalty into the rollouts), from the trace's `XLA Modules` line.
The host phases cannot see this time: it runs inside `rollout`, behind the
sampler (PERF.md section 5)."""

from benchmark.layer_metrics._device_seconds import busy_share

PROGRAMS = ("jit_ppo_experience_fwd", "jit_ppo_score_inject")


def read(r):
    if not r.trace:
        return None
    return busy_share(r.trace, sum(r.trace["programs_s"].get(p, 0.0) for p in PROGRAMS))
