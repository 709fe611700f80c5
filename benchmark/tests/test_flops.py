"""benchmark/flops.py against counts made by hand for a 2-layer model."""

import pytest

from benchmark import flops

# E=8, 2 heads of 4, MLP 16 wide with two matrices, vocabulary 32
D = dict(n_layer=2, hidden=8, n_head=2, n_kv_head=2, head_dim=4, intermediate=16,
         mlp_matrices=2, vocab=32, tied=False)
# one token through one block: q, k, v, o are 8x8 each, the MLP 8x16 twice
LINEAR = 2 * (4 * 8 * 8 + 2 * 8 * 16)


def test_layer_linear_of_the_description_derived_from_dims():
    w = flops.describe(D)
    assert len(w["layers"]) == 2 and w["leading"] == 0 and flops.describe(w) is w
    assert flops.layer_linear_flops(w["layers"][0]) == LINEAR == 1024
    assert w["layers"][0]["pair_flops"] == 4 * 2 * 4 and w["layers"][0]["cache_elems"] == 2 * 2 * 4
    assert w["head"] == {"flops": 2.0 * 8 * 32, "weight_elems": 8 * 32}


def test_causal_forward_counts_the_lower_triangle():
    # 3 tokens: query t sees t keys: 1 + 2 + 3 = 6 (query, key) pairs, each two
    # 4-wide dot products (score, weighted value) per head = 4 * 2 * 4 FLOPs
    attn = 6 * 4 * 2 * 4
    assert flops.causal_forward_flops(D, 3, 2) == 2 * (3 * LINEAR + attn)


def test_generation_is_prefill_plus_steps():
    # prompt 3, 3 new tokens: prefill of 3 gives the first token, then two steps
    # that see 4 and 5 keys; logits at 1 + 2 positions
    attn = (6 + 4 + 5) * 4 * 2 * 4
    want = 2 * ((3 + 2) * LINEAR + attn) + 3 * 2 * 8 * 32
    assert flops.generation_flops(D, 3, 3) == want


@pytest.mark.parametrize("unfrozen, trained", [(1, 1), (-1, 2), (5, 2)])
def test_training_backward_only_through_trainable_layers(unfrozen, trained):
    seq, new = 5, 2
    fwd = flops.causal_forward_flops(D, seq, 2)
    bwd = 2 * flops.causal_forward_flops(D, seq, trained)
    head = 3 * 2 * 8 * 32 * new  # forward and two gradient matmuls, response only
    assert flops.ppo_train_flops(D, 3, 2, unfrozen) == fwd + bwd + head


def test_cycle_is_the_sum_of_its_phases():
    traffic = dict(prompt_tokens=3, new_tokens=2, rollouts=4, method_kwargs=dict(ppo_epochs=3))
    c = flops.ppo_cycle_flops(D, traffic, 1)
    assert c["generation"] == 4 * flops.generation_flops(D, 3, 2)
    assert c["scoring"] == 4 * (
        flops.causal_forward_flops(D, 5, 2) + flops.causal_forward_flops(D, 5, 1)
        + 2 * 2 * 8 * 32 * 2)
    assert c["training"] == 3 * 4 * flops.ppo_train_flops(D, 3, 2, 1)
    assert c["total"] == c["generation"] + c["scoring"] + c["training"]


def test_kernel_counts_and_roofline():
    f = flops.flash_fwd(batch=1, heads=2, kv_heads=2, seq=4, head_dim=4)
    assert f["flops"] == 4 * 2 * 4 * (4 * 5 / 2)  # 10 visible pairs per head
    assert f["bytes"] == 4 * (2 * 4 * 4) * 2  # q, k, v, o: 2 heads of 4x4 each, bf16
    assert flops.flash_bwd(1, 2, 2, 4, 4)["flops"] == 2.5 * f["flops"]
    peak = dict(bf16_flops_per_s=100.0, hbm_bytes_per_s=10.0)
    assert flops.roofline_seconds(dict(flops=1000.0, bytes=10.0), peak) == dict(seconds=10.0, bound="flops")
    assert flops.roofline_seconds(dict(flops=10.0, bytes=1000.0), peak) == dict(seconds=100.0, bound="bytes")


def test_decode_step_bytes():
    # weights once (int8), output projection once (bf16), K and V of every row
    block = 4 * 8 * 8 + 2 * 8 * 16
    want = 2 * block * 1 + 8 * 32 * 2 + 2 * 2 * 3 * 2 * 4 * 10 * 1
    assert flops.decode_step_bytes(D, batch=3, keys=10) == want
