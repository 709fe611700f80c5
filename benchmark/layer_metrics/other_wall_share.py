"""Share of the window's host wall in the program's `other` phase (innermost
phase wins, so `reward` is not counted under `rollout`)."""

from benchmark.layer_metrics._shares import phase_share


def read(r):
    return phase_share(r, "other")
