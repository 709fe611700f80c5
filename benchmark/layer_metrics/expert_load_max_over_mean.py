"""The straggler among the held experts: the rows of the fullest held expert over
the mean of the held experts, in the worst routed layer (the program's counter
`moe/load_max_over_mean`, carried out of the sampler, the scorer and the train
step), as the mean over the window's cycles of the worst of the three programs.
1.0 is an even load; the grouped products wait for the fullest expert's tiles."""

from benchmark.layer_metrics import _routed


def read(r):
    values = [_routed.counter(r, f"moe/load_max_over_mean.{p}") for p in ("sampler", "scorer", "train")]
    values = [v for v in values if v is not None]
    return max(values) if values else None
