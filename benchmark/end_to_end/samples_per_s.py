"""Rollouts of one cycle over the median wall of the window's whole cycles.
A cycle collects the rollouts, rewards and scores them and trains on them for
every PPO epoch, through `learn()`; its wall is host clock from one boundary
to the next, each taken once the device had finished the cycle's last
optimizer step. The median, because one cycle in about fifteen is late by 0.1 s
and one in a hundred by over a second on a shared host (PERF.md, PR 22): the
sum of the walls would carry that into the metric."""


def read(r):
    return r.traffic["rollouts"] / r.cycle_s
