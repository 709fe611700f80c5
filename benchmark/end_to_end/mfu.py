"""Model FLOP/s utilization of the whole cycle, in percent of the bf16 peak
of the chips used: the operations one cycle of the traffic mix requires
(benchmark/flops.py: generation, scoring with the frozen reference branch,
training forward and the backward of the trainable layers; no credit for
recomputation or for gradients of frozen layers) over the median cycle wall.
Inside one cell it moves exactly as samples_per_s does; it is what compares
cells, configurations and chip counts."""

from benchmark import flops


def read(r):
    cycle = getattr(flops, r.traffic["method"] + "_cycle_flops")(
        r.cell.reference.dims(r.hf), r.traffic, r.unfrozen)
    achieved = cycle["total"] / r.cycle_s
    return 100.0 * achieved / (r.chips * r.peaks["bf16_flops_per_s"])
