"""Tokens through the optimizer steps of the window (steps x batch rows x
sequence length) over the host wall of the fused block and the wait for
it."""


def read(r):
    wall = r.phases.get("fused_block", 0.0) + r.phases.get("train_wait", 0.0)
    if wall <= 0:
        return None
    seq = r.traffic["prompt_tokens"] + r.traffic["new_tokens"]
    return len(r.cycles) * r.steps_per_cycle * r.traffic["batch"] * seq / wall
