"""Model FLOP/s utilization of the whole cycle, in percent of the bf16 peak
of the chips used: the operations one cycle of the traffic mix requires
(benchmark/flops.py: generation, scoring with the frozen reference branch,
training forward and the backward of the trainable layers; no credit for
recomputation or for gradients of frozen layers) over the median cycle wall.
Inside one cell it moves exactly as samples_per_s does; it is what compares
cells, configurations and chip counts.

The operations are summed over the description the configuration's reference
module gives of its layers (`flops.work`). For a layer with routed experts that
count is an expectation, `experts_per_token x held / published` routed experts a
token under uniform routing, not what the router did in this run: the program's
per-layer counter of tokens routed to the experts held here is what a later
per-layer reader holds against it."""

from benchmark import flops


def read(r):
    cycle = getattr(flops, r.traffic["method"] + "_cycle_flops")(
        flops.work(r.cell.reference, r.hf), r.traffic, r.unfrozen)
    achieved = cycle["total"] / r.cycle_s
    return 100.0 * achieved / (r.chips * r.peaks["bf16_flops_per_s"])
