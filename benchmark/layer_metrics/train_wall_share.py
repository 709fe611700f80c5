"""Share of the window's host wall spent dispatching the fused block (the
program's `fused_block` phase: batch placement, dispatch, the deferred
stats flush) and waiting for it to finish (`train_wait`, the harness's own
block_until_ready at the cycle boundary). Scoring work still queued on the
device when the block is dispatched is inside this wait too: the program
has no span for it."""

from benchmark.layer_metrics._shares import phase_share


def read(r):
    return phase_share(r, "fused_block", "train_wait")
