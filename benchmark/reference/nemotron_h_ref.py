"""Plain Nemotron-H reference: float32 `jax.numpy`, no kernels, no cache, no chunks.

Written from the published `config.json` keys (model_type `nemotron_h`) and the
equations they name, and independent of `trlx_tpu/models/transformer.py`: nothing
here imports the program. d = hidden_size.

EVERY LAYER IS ONE SUB-LAYER: x <- x + F(RMSNorm(x)), eps `layer_norm_epsilon`, no
bias anywhere but the convolution's; F by the layer's letter in
`hybrid_override_pattern` (`M` a Mamba-2 mixer, `*` attention, `E` routed experts;
nothing pairs an `M` with the `E` above it). After the last layer the final RMSNorm,
then an untied head.

`M`, Mamba-2: H = `mamba_num_heads` heads of P = `mamba_head_dim` (H P = `expand` d),
G = `n_groups` groups of N = `ssm_state_size` (a group serves H / G heads), per
position t and head h, as a plain RECURRENCE (a `lax.scan` over positions):

    [z | xBC | dt] = x W_in                          (H P | H P + 2 G N | H)
    xBC = SiLU(Conv(xBC) + b_conv)
          Conv: causal depthwise, y_t[c] = sum_{i=0..taps-1} w[i, c] u_{t-(taps-1)+i}[c],
          taps = `conv_kernel`, zeros before the start
    [x' | B | C] = xBC                               x' [H, P]; B, C [G, N]
    dt = softplus(dt + dt_bias),  A = -exp(A_log)    one number a head
    h_t = exp(dt_t A) h_{t-1} + dt_t x'_t (x) B_t    h [P, N], h_0 = 0
    y_t = h_t C_t + D x'_t
    y = w * RMSNorm_groups(y * SiLU(z))              the norm over each group's H P / G channels
    out = y W_out

A position whose mask is 0 changes nothing: its convolution input is zero, what the
convolution gives there is zeroed (x' = B = C = 0) and its dt is 0.

`*`, attention: q, k, v, o without bias, `num_attention_heads` query heads over
`num_key_value_heads` key-value heads of `head_dim`, scale head_dim^-0.5, causal, NO
positional encoding (departure noted under `assumed`: the family's published modelling
code rotates nothing in its attention layers; `rope_theta` and `partial_rotary_factor`
are kept as published and not read).

`E`, latent-space experts (`moe_latent_size`), relu2(a) = max(a, 0)^2, no gate matrix:

    s = sigmoid(x W_g)                               `n_routed_experts_published` wide
    chosen = top-k of (s + b),  k = num_experts_per_tok         (n_group 1: no grouping)
    w = routed_scaling_factor * s[chosen] / (sum(s[chosen]) + 1e-20)       (norm_topk_prob)
    u = x W_down                                     d -> moe_latent_size
    r = sum_{e in chosen, e HELD HERE} w_e W2_e relu2(W1_e u)    latent -> moe_intermediate_size -> latent
    y = r W_up + W2_s relu2(W1_s x)                  the shared expert reads x, width
                                                     moe_shared_expert_intermediate_size

THE SHARE: the configuration's file gives under `n_routed_experts` the experts held on
this chip (experts first_expert_held ...), under `n_routed_experts_published` the
router's width, and a vocabulary slice. The reference routes over all published
experts and adds the held experts' part alone; what the absent experts would add is
left out, here and in the program. W_up is linear, so the shares' r W_up add up to the
uncut layer's.

NOT RUN: the next-token-plus-one module (`num_nextn_predict_layers` 1 -> 0,
`mtp_hybrid_override_pattern` kept and not read): PPO neither samples with it nor
trains it. Other departures, each listed in the configuration's `assumed` or
`reduced`: initial values the config does not give are the program's.

`hidden_states` also returns, per position, whether its routing was DECISIVE: in
every `E` layer, either the k-th and (k+1)-th of (s + b) lie more than
`correct.routing_margin` apart, or no expert held here lies within that margin of
the boundary between them.

Every matmul runs under `jax.default_matmul_precision("highest")`. On the chip it
fits as it is: the recurrence holds one [rows, heads, P, N] state, the experts run
one held expert at a time over every position.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

KINDS = {"M": ("ssm", "none"), "*": ("softmax", "none"), "E": ("none", "routed")}


def pattern(hf: Dict) -> str:
    """The letters of the layers run."""
    letters = hf["hybrid_override_pattern"]
    if len(letters) != hf["num_hidden_layers"] or set(letters) - set(KINDS):
        raise ValueError(
            f"hybrid_override_pattern {letters!r} names one of M, * and E for each of the "
            f"{hf['num_hidden_layers']} layers")
    return letters


def system_config(hf: Dict) -> Dict:
    """The published keys as the keyword arguments of the system's
    `TransformerConfig` (the only place that knows both names)."""
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise NotImplementedError("grouped top-k with more than one group")
    if hf["expand"] * hf["hidden_size"] != hf["mamba_num_heads"] * hf["mamba_head_dim"]:
        raise ValueError("expand x hidden_size is not mamba_num_heads x mamba_head_dim")
    if not hf["use_conv_bias"] or hf["mamba_proj_bias"] or hf["attention_bias"] or hf["mlp_bias"] \
            or hf["mamba_hidden_act"] != "silu" or not hf["norm_topk_prob"]:
        raise NotImplementedError("a bias, activation or router weighting other than the published ones")
    letters = pattern(hf)
    return dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        intermediate_size=hf["intermediate_size"],
        pos_embed="none",
        rope_theta=float(hf["rope_theta"]),
        norm="rmsnorm",
        layer_norm_epsilon=hf["layer_norm_epsilon"],
        activation=hf["mlp_hidden_act"],
        mlp_gated=False,
        use_attn_bias=False,
        use_mlp_bias=False,
        use_norm_bias=False,
        tie_word_embeddings=hf["tie_word_embeddings"],
        mixer_layers=tuple(KINDS[c][0] for c in letters),
        ffn_layers=tuple(KINDS[c][1] for c in letters),
        ssm_heads=hf["mamba_num_heads"],
        ssm_head_dim=hf["mamba_head_dim"],
        ssm_state=hf["ssm_state_size"],
        ssm_groups=hf["n_groups"],
        ssm_conv=hf["conv_kernel"],
        ssm_chunk=hf["chunk_size"],
        ssm_dt_min=hf["time_step_min"],
        ssm_dt_max=hf["time_step_max"],
        ssm_dt_floor=hf["time_step_floor"],
        n_routed_experts=hf["n_routed_experts_published"],
        n_experts_held=hf["n_routed_experts"],
        first_expert_held=hf.get("first_expert_held", 0),
        n_experts_per_token=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        moe_latent_size=hf["moe_latent_size"],
        moe_gated=False,
        moe_shared_intermediate_size=hf["moe_shared_expert_intermediate_size"],
        n_shared_experts=hf["n_shared_experts"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        router_balance_steps=hf.get("router_bias_balance_steps", 0),
    )


def toy_sizes(hf: Dict) -> Dict:
    """The overrides `--rehearse` runs this family at: every mechanism, no cost.
    M E M * E M E: a top-2 branch is one `M` and one `E` layer, the attention layer and
    two of each other kind lie under it; chunks of 16 so that a sequence is several; a
    token is sent to more experts (6) than are held here (4), as 22 against 8."""
    return {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
            "intermediate_size": 32, "expand": 2, "mamba_num_heads": 8, "mamba_head_dim": 16,
            "n_groups": 2, "ssm_state_size": 16, "chunk_size": 16,
            "moe_intermediate_size": 32, "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 48,
            "num_hidden_layers": 7, "hybrid_override_pattern": "MEM*EME",
            "n_routed_experts": 4, "n_routed_experts_published": 16, "num_experts_per_tok": 6,
            "vocab_size": 512}


# -- the work, for benchmark/flops.py ----------------------------------------


def _elems(hf: Dict) -> Dict[str, int]:
    """Weight elements of one layer's parts, from the published keys."""
    d = hf["hidden_size"]
    inner = hf["mamba_num_heads"] * hf["mamba_head_dim"]
    conv = inner + 2 * hf["n_groups"] * hf["ssm_state_size"]
    heads, kv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    return {
        # Mamba-2: W_in and W_out, int8 in the rollout
        "ssm_written": d * (inner + conv + hf["mamba_num_heads"]) + inner * d,
        # taps and their bias, A_log, D, dt_bias, the gated norm's weight: float32, elementwise
        "ssm_vectors": conv * (hf["conv_kernel"] + 1) + 3 * hf["mamba_num_heads"] + inner,
        "attention": d * hd * (2 * heads + 2 * kv),  # q and o, k and v
        "router": d * hf["n_routed_experts_published"],
        "latent_pair": 2 * d * hf["moe_latent_size"],
        "shared": 2 * d * hf["moe_shared_expert_intermediate_size"] * hf["n_shared_experts"],
        "expert": 2 * hf["moe_latent_size"] * hf["moe_intermediate_size"],
    }


def work(hf: Dict) -> Dict:
    """The layers one by one, as the doc-string of `benchmark/flops.py` sets out: a
    layer here is ONE sub-layer. `linear_flops` of an `M` layer: its two projections
    and, a head, the rank-one update and the read-out of the recurrence (4 P N a
    token), the least any form needs; `pair_flops` 0 and `cache_elems` 0 there (its
    state does not grow). An `E` layer: router, both latent projections and the shared
    expert as `linear_flops`, one routed expert's two products under `routed`.
    `weight_elems` in units of the item size the recipe decodes with: under int8
    rollout weights the float32 vectors and the router count four times."""
    e = _elems(hf)
    int8 = (hf.get("recipe", {}).get("model", {}).get("model_extra_configs", {})
            .get("transformer", {}).get("decode_weights_quant") == "int8")
    f32 = 4 if int8 else 2
    recurrence = 4.0 * hf["mamba_num_heads"] * hf["mamba_head_dim"] * hf["ssm_state_size"]
    kinds = {
        "M": dict(linear_flops=2.0 * e["ssm_written"] + recurrence,
                  weight_elems=e["ssm_written"] + f32 * e["ssm_vectors"], pair_flops=0.0, cache_elems=0),
        "*": dict(linear_flops=2.0 * e["attention"], weight_elems=e["attention"],
                  pair_flops=4.0 * hf["num_attention_heads"] * hf["head_dim"],
                  cache_elems=2 * hf["num_key_value_heads"] * hf["head_dim"]),
        "E": dict(linear_flops=2.0 * (e["router"] + e["latent_pair"] + e["shared"]),
                  weight_elems=e["latent_pair"] + e["shared"] + f32 * e["router"],
                  pair_flops=0.0, cache_elems=0,
                  routed={"expert_flops": 2.0 * e["expert"], "expert_elems": e["expert"],
                          "published": hf["n_routed_experts_published"], "held": hf["n_routed_experts"],
                          "per_token": hf["num_experts_per_tok"]}),
    }
    head = hf["hidden_size"] * hf["vocab_size"]
    return {"layers": [dict(kinds[c]) for c in pattern(hf)], "leading": 0,
            "head": {"flops": 2.0 * head, "weight_elems": head}}


def params_held(hf: Dict) -> Dict[str, int]:
    """Parameters held here by kind of layer, each with its one norm."""
    e, d = _elems(hf), hf["hidden_size"]
    ssm = e["ssm_written"] + e["ssm_vectors"] + d
    attention = e["attention"] + d
    rest = e["router"] + hf["n_routed_experts_published"] + e["latent_pair"] + e["shared"] + d
    experts = rest + hf["n_routed_experts"] * e["expert"]
    embed = 2 * d * hf["vocab_size"]
    letters = pattern(hf)
    total = (embed + d + letters.count("M") * ssm + letters.count("*") * attention
             + letters.count("E") * experts)
    return {"ssm_layer": ssm, "attention_layer": attention, "expert_layer": experts,
            "expert_layer_without_routed": rest, "routed_expert": e["expert"],
            "embed_and_head": embed, "total": total}


# -- the program's tree under this file's names -------------------------------


def params_from_system(base: Dict) -> Dict:
    """The system's language-model tree (`params["base"]`: the `M` layers stacked
    under `ssm_blocks`, the `*` layers under `attn_blocks`, the `E` layers under
    `moe_blocks`, each in layer order) renamed to this file's layout. No arithmetic.
    `hidden_states` walks the pattern and takes each layer from its stack's next row."""
    out = {"embed": base["embed"]["wte"], "lnf": base["ln_f"]["scale"], "unembed": base["lm_head"]["kernel"]}
    if "ssm_blocks" in base:
        blk, ssm = base["ssm_blocks"], base["ssm_blocks"]["ssm"]
        out["M"] = {"norm": blk["ln_1"]["scale"], "w_in": ssm["in_proj"]["kernel"], "w_out": ssm["out_proj"]["kernel"],
                    "conv_w": ssm["conv_w"], "conv_b": ssm["conv_b"], "a_log": ssm["A_log"],
                    "dt_bias": ssm["dt_bias"], "d": ssm["D"], "gate_norm": ssm["norm"]}
    if "attn_blocks" in base:
        blk, attn = base["attn_blocks"], base["attn_blocks"]["attn"]
        out["*"] = {"norm": blk["ln_1"]["scale"], "w_q": attn["q"]["kernel"], "w_k": attn["k"]["kernel"],
                    "w_v": attn["v"]["kernel"], "w_o": attn["o"]["kernel"]}  # [L, d, heads, hd]; w_o [L, heads, hd, d]
    if "moe_blocks" in base:
        blk, moe = base["moe_blocks"], base["moe_blocks"]["moe"]
        out["E"] = {"norm": blk["ln_2"]["scale"], "w_r": moe["router_gate"], "b": moe["router_bias"],
                    "w_down": moe["latent_in"]["kernel"], "w_up": moe["latent_out"]["kernel"],
                    "w_1": moe["experts_fc_in"]["kernel"], "w_2": moe["experts_fc_out"]["kernel"],  # [L, held, ...]
                    "shared_1": moe["shared"]["fc_in"]["kernel"], "shared_2": moe["shared"]["fc_out"]["kernel"]}
    return out


# -- the forward ---------------------------------------------------------------


def _rms(x, g, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if g is None else y * g


def relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def _conv(u, w, b):
    """u [B, T, C], w [taps, C], b [C]: y_t = sum_i w[i] u_{t - (taps - 1) + i} + b, zeros before the start."""
    taps, T = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[i] * padded[:, i : i + T] for i in range(taps)) + b


def _recurrence(x, b, c, dt, a):
    """x [B, T, H, P], b, c [B, T, H, N] (each head its group's), dt [B, T, H], a [H]
    -> y [B, T, H, P]: the state space one position at a time, h_0 = 0."""
    B, T, H, P = x.shape

    def step(h, at):
        x, b, c, dt = at
        h = jnp.exp(dt * a)[..., None, None] * h + (dt[..., None] * x)[..., None] * b[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c)

    h0 = jnp.zeros((B, H, P, b.shape[-1]), jnp.float32)
    y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt)))[1]
    return jnp.moveaxis(y, 0, 1)


def _mamba(x, w, hf, mask):
    B, T, _ = x.shape
    H, P, G, N = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["n_groups"], hf["ssm_state_size"]
    inner = H * P
    live = mask.astype(jnp.float32)[..., None]
    zxbcdt = x @ w["w_in"]
    z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner : 2 * inner + 2 * G * N], zxbcdt[..., 2 * inner + 2 * G * N :]
    xbc = jax.nn.silu(_conv(xbc * live, w["conv_w"], w["conv_b"])) * live
    xs = xbc[..., :inner].reshape(B, T, H, P)
    per_head = lambda v: jnp.repeat(v.reshape(B, T, G, N), H // G, axis=2)  # a group serves H / G heads
    b, c = per_head(xbc[..., inner : inner + G * N]), per_head(xbc[..., inner + G * N :])
    dt = jax.nn.softplus(dt + w["dt_bias"]) * live
    y = _recurrence(xs, b, c, dt, -jnp.exp(w["a_log"])) + w["d"][:, None] * xs
    y = y.reshape(B, T, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(B, T, G, inner // G), None, hf["layer_norm_epsilon"]).reshape(B, T, inner) * w["gate_norm"]
    return y @ w["w_out"]


def _attention(x, w, hf, visible):
    heads, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    q = jnp.einsum("btd,dhk->bthk", x, w["w_q"])
    k = jnp.repeat(jnp.einsum("btd,dhk->bthk", x, w["w_k"]), heads // kv, axis=2)
    v = jnp.repeat(jnp.einsum("btd,dhk->bthk", x, w["w_v"]), heads // kv, axis=2)
    s = jnp.einsum("bthk,bshk->bhts", q, k) / math.sqrt(hf["head_dim"])
    s = jnp.where(visible, s, jnp.finfo(jnp.float32).min)
    o = jnp.einsum("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bthk,hkd->btd", o, w["w_o"])


def route(x, w, hf):
    """(weights [B, T, published] with zeros off the chosen, values s + b)."""
    k = hf["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["w_r"])
    values = s + w["b"]
    kth = jnp.sort(values, axis=-1)[..., -k][..., None]
    picked = jnp.where(values >= kth, s, 0.0)
    weights = hf["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return weights, values


def shared_expert(x, w):
    return relu2(x @ w["shared_1"]) @ w["shared_2"]


def _experts(x, w, hf, margin):
    """The shared expert plus the HELD experts' part brought back from the latent
    space, and per position whether the routing was decisive (module doc-string). One
    held expert at a time, every position through it, weighted by its routing weight
    (zero where it was not chosen)."""
    first, held, k = hf.get("first_expert_held", 0), hf["n_routed_experts"], hf["num_experts_per_tok"]
    weights, values = route(x, w, hf)
    u = x @ w["w_down"]
    r = jnp.zeros_like(u)
    for e in range(held):
        r = r + weights[..., first + e, None] * (relu2(u @ w["w_1"][e]) @ w["w_2"][e])
    y = r @ w["w_up"]
    if hf["n_shared_experts"]:
        y = y + shared_expert(x, w)
    ordered = jnp.sort(values, axis=-1)
    kth, nxt = ordered[..., -k], ordered[..., -k - 1]
    boundary = 0.5 * (kth + nxt)[..., None]
    near_held = (jnp.abs(values - boundary) <= margin)[..., first : first + held].any(-1)
    return y, ((kth - nxt) > margin) | ~near_held


def hidden_states(p: Dict, hf: Dict, tokens, mask):
    """(final-norm hidden states [B, T, d], decisive [B, T]) for `tokens` [B, T]
    under the padding `mask` [B, T] (1 = real token)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    eps = hf["layer_norm_epsilon"]
    margin = hf.get("correct", {}).get("routing_margin", 0.0)
    T = tokens.shape[1]
    visible = jnp.tril(jnp.ones((T, T), bool))[None, None] & (mask[:, None, None, :] > 0)
    taken = {"M": 0, "*": 0, "E": 0}
    with jax.default_matmul_precision("highest"):
        x = p["embed"][tokens]
        decisive = jnp.ones(tokens.shape, bool)
        for letter in pattern(hf):
            w = jax.tree_util.tree_map(lambda a: a[taken[letter]], p[letter])
            taken[letter] += 1
            h = _rms(x, w["norm"], eps)
            if letter == "M":
                x = x + _mamba(h, w, hf, mask)
            elif letter == "*":
                x = x + _attention(h, w, hf, visible)
            else:
                y, sure = _experts(h, w, hf, margin)
                x, decisive = x + y, decisive & sure
        return _rms(x, p["lnf"], eps), decisive


def logits(p: Dict, hidden):
    """Untied output projection over the vocabulary slice, float32 [..., V]."""
    with jax.default_matmul_precision("highest"):
        return hidden.astype(jnp.float32) @ p["unembed"].astype(jnp.float32)
