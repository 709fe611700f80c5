"""Percent of their roofline the grouped products over the held experts reach:
the least time the chip could take for the token-expert pairs the program
COUNTED on this chip (`moe/assignments_here`, by program, from the flight
stream), over the device seconds under the scope `moe_experts` (sort, grouped
products, combine) in the traced cycles. Required: one expert's three products
for every counted pair in the sampler, the scorer and the train step's forward,
twice that again for the backward of the trainable layers' share of the train
step's pairs; and each held expert's weights read once a layer a call (of the
decode steps, the experts a batch of rows is expected to reach, as
`flops.decode_step_bytes` has it), at the width the call computes in. Sort,
gather and combine are what the program adds: the share cannot pass 100%."""

from benchmark import flops, trace_reduce
from benchmark.layer_metrics import _routed


def read(r):
    if not r.trace:
        return None
    took = trace_reduce.scope_seconds(r.trace, "moe_experts")
    _, routed_layers = _routed.layers(r)
    if not took or not routed_layers:
        return None
    here = {p: _routed.counter(r, f"moe/assignments_here.{p}") for p in ("sampler", "scorer", "train")}
    if None in here.values():
        return None
    routed, n_routed = routed_layers[0]["routed"], len(routed_layers)
    calls, t = _routed.cycle_calls(r), r.traffic
    trainable = flops.trainable_layers(flops.work(r.cell.reference, r.hf), r.unfrozen)
    held_elems = routed["held"] * routed["expert_elems"]
    quant = r.cell.config["recipe"].get("model", {}).get("model_extra_configs", {}).get("transformer", {})
    decode_itemsize = 1 if quant.get("decode_weights_quant") == "int8" else 2
    reached = 1.0 - (1.0 - routed["per_token"] / routed["published"]) ** t["chunk"]
    phases = [
        # prefill and decode steps: pairs as counted; weights once for the prefill, then a step's
        {"flops": calls["sampler"] * here["sampler"] * routed["expert_flops"],
         "bytes": calls["sampler"] * n_routed * held_elems * decode_itemsize * (
             1 + (t["new_tokens"] - 1) * reached)},
        # the policy's layers and the reference branch's, in the compute dtype
        {"flops": calls["scorer"] * here["scorer"] * routed["expert_flops"],
         "bytes": calls["scorer"] * (n_routed + trainable) * held_elems * 2},
        # forward over every routed layer, backward (twice the forward) over the trainable ones
        {"flops": calls["train"] * here["train"] * routed["expert_flops"] * (1 + 2.0 * trainable / n_routed),
         "bytes": calls["train"] * (n_routed + 2 * trainable) * held_elems * 2},
    ]
    least = sum(flops.roofline_seconds(p, r.peaks)["seconds"] for p in phases)
    return 100.0 * t["trace_cycles"] * least / r.chips / took
