"""Arithmetic shared by the flash-attention roofline readers."""

from benchmark import flops


def kernel_seconds(r, *prefixes):
    """Self seconds a chip spent in the instructions whose names start
    with one of `prefixes`, over the traced cycles (`ops_by_self_time`
    holds the mean over chips). None where the trace has none."""
    times = [t for name, t in r.trace["ops_by_self_time"] if name.startswith(prefixes)]
    return sum(times) if times else None


def least_seconds(r, kernel, seq, layer_calls):
    """Least seconds a chip could take for `layer_calls` calls of `kernel`
    (`flops.flash_fwd` or `flops.flash_bwd`), each over every rollout of a
    cycle at `seq` tokens, in each of the traced cycles."""
    d = r.cell.reference.dims(r.hf)
    work = kernel(r.traffic["rollouts"], d["n_head"], d["n_kv_head"], seq, d["head_dim"])
    one = flops.roofline_seconds(work, r.peaks)["seconds"]
    return r.traffic["trace_cycles"] * layer_calls * one / r.chips
