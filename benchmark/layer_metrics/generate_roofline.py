"""Percent of its roofline the cycle's generation reaches: the least time
the chips could take (prefill FLOPs at the bf16 peak, then for every decode
step the bytes that step must read, `flops.decode_step_bytes`, at the HBM
peak; over chips x peak, as `mfu` counts) over the seconds of the program's
`generate` + `tokens_wait` spans in the window. Decode is bound by bytes,
prefill by FLOPs; the sum is the bound of doing one after the other."""

from benchmark import flops
from benchmark.layer_metrics._program_spans import span_seconds


def least_seconds(r):
    """Least seconds for one cycle's generation on `r.chips` chips."""
    w = flops.work(r.cell.reference, r.hf)
    t = r.traffic
    p, n, rows = t["prompt_tokens"], t["new_tokens"], t["rollouts"]
    quant = r.cell.config["recipe"].get("model", {}).get(
        "model_extra_configs", {}).get("transformer", {})
    weight_itemsize = 1 if quant.get("decode_weights_quant") == "int8" else 2
    kv_itemsize = 1 if str(quant.get("kv_cache_quant")).startswith("int8") else 2
    prefill = rows * (flops.causal_forward_flops(w, p) + flops.logits_flops(w, 1))
    # step i of n - 1 attends to the prompt and the i tokens before it
    decode = sum(
        flops.decode_step_bytes(w, rows, p + i, weight_itemsize, kv_itemsize)
        for i in range(1, n)
    )
    return (prefill / r.peaks["bf16_flops_per_s"] + decode / r.peaks["hbm_bytes_per_s"]) / r.chips


def read(r):
    seconds = span_seconds(r, ("generate", "tokens_wait"))
    if not seconds:
        return None
    return 100.0 * len(r.cycles) * least_seconds(r) / seconds
