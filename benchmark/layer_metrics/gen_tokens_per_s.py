"""Generated tokens of the window over the host wall of the `rollout`
phase, reward calls excluded. The phase also holds decoding the tokens to
text and dispatching the scoring forward, so this is the rate the trainer
sees, below the sampler's own."""


def read(r):
    wall = r.phases.get("rollout", 0.0)
    if wall <= 0:
        return None
    return len(r.cycles) * r.traffic["rollouts"] * r.traffic["new_tokens"] / wall
