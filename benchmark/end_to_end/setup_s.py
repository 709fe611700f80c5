"""Process start to the start of the window: imports, weights from the
seed, every program compiled or read from the cache, the first rollouts,
the reference check, learn()'s first evaluation and the warm-up block."""


def read(r):
    return r.setup_s
