"""Percent of the window's host wall inside the program's `generate` and
`tokens_wait` spans: the sampler timed at its own sync (dispatch, then the
blocking pull of the sampled tokens). With one chunk a cycle this is the
sampler's device time; `rollout_wall_share` beside it also holds decoding to
text, the reward call's surroundings and the scoring dispatch."""

from benchmark.layer_metrics._program_spans import span_seconds


def read(r):
    seconds = span_seconds(r, ("generate", "tokens_wait"))
    if seconds is None or r.wall_s <= 0:
        return None
    return 100.0 * seconds / r.wall_s
