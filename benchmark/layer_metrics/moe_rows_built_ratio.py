"""Rows the routed layers' sorted form built for each token-expert pair computed on
this chip, in the scorer and the train step together: the program's counters
`moe/rows_built` over `moe/assignments_here`, each program weighed by how often a
cycle runs it. A row for every pair computed here reads 1; a form bounded at twice
the pairs expected here reads about 2; the form that builds every row that can be
held here reads min(K, held) x published / (K x held) under uniform routing (8 to
32 in the routed cells). A program that does not count `moe/rows_built` builds
that whole form: its rows are `moe/assignments` x min(K, held) / K. None for a
family without routed layers, or a program without the counters."""

from benchmark.layer_metrics import _routed


def read(r):
    _, routed_layers = _routed.layers(r)
    if not routed_layers:
        return None
    routed = routed_layers[0]["routed"]
    whole_rows_a_pair = min(routed["per_token"], routed["held"]) / routed["per_token"]
    calls = _routed.cycle_calls(r)
    built = here = 0.0
    for program in ("scorer", "train"):
        pairs_here = _routed.counter(r, f"moe/assignments_here.{program}")
        rows = _routed.counter(r, f"moe/rows_built.{program}")
        if rows is None:
            made = _routed.counter(r, f"moe/assignments.{program}")
            rows = None if made is None else made * whole_rows_a_pair
        if pairs_here is None or rows is None:
            return None
        built += calls[program] * rows
        here += calls[program] * pairs_here
    return built / here if here else None
