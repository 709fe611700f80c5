"""Peak device memory of the fused train step, the cycle's largest
program, as the compiler plans it (`memory_analysis().peak_memory_in_bytes`),
per device. `memory_stats()` leaves the
program's temporaries out (CHANGES.md PR 21, item d); the run line's
`memory_peak_bytes` carries that figure."""


def read(r):
    if not r.memory:
        return None
    return r.memory["peak"] / 2**30
