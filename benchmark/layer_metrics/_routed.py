"""What the readers of a routed, latent-attention model share: the counters the
program carries out of its jitted programs (`moe/*`: the sampler's ride the
`tokens_wait` span's counts, the scorer's and the train step's the cycle row's
`counters`, a row after the work they count), and the family's layers as
`flops.work` has them. Everything returns None where there is nothing to read:
another family, or a program without these counters."""

from benchmark import flops
from benchmark.layer_metrics._program_spans import window_rows


def layers(r):
    """(all layers, the routed ones) of the family's `work`, or (None, None)
    for a module that states only `dims`."""
    if not hasattr(r.cell.reference, "work"):
        return None, None
    all_layers = flops.work(r.cell.reference, r.hf)["layers"]
    return all_layers, [layer for layer in all_layers if layer.get("routed")]


def counter(r, name):
    """Mean over the window's cycles of the counter `name` (`moe/assignments_here.sampler`),
    wherever the row keeps it, or None."""
    values = []
    for row in window_rows(r):
        if name in (row.get("counters") or {}):
            values.append(row["counters"][name])
        values += [counts[name] for *_, counts in row.get("spans") or []
                   if isinstance(counts, dict) and name in counts]
    return sum(values) / len(values) if values else None


def cycle_calls(r):
    """How often a cycle runs each program: the sampler and the scorer once a
    chunk, the train step `steps_per_cycle` times (its counters are a step's)."""
    t = r.traffic
    chunks = max(t["rollouts"] // t["chunk"], 1)
    steps = t["method_kwargs"]["ppo_epochs"] * max(t["rollouts"] // t["batch"], 1)
    return {"sampler": chunks, "scorer": chunks, "train": steps}
