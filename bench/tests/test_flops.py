"""Operation and byte counts against hand counts at a small size."""

import pytest

from benchlib import flops

M = {"hidden_size": 8, "intermediate_size": 12, "num_attention_heads": 4,
     "num_key_value_heads": 2, "head_dim": 2, "num_hidden_layers": 3,
     "vocab_size": 10}


def test_linear_flops_per_token():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, MLP 3 x 8x12 = 64+32+32+64+288
    assert flops.linear_flops_per_token(M) == 2 * 3 * 480


def test_chunk_flops_counts_causal_pairs_and_one_logits_row():
    # 3 tokens after 5 of history see 6, 7 and 8 keys: 21 pairs
    assert flops.causal_pairs(3, 5) == 21
    attn = 4 * 3 * 4 * 2 * 21            # QK and PV, 3 layers, 4 heads x 2
    assert flops.chunk_flops(M, 3, 5) == 2 * 3 * 480 * 3 + attn + 2 * 8 * 10


def test_tick_flops_live_rows_only():
    # two live rows with 4 and 9 cached tokens attend 5 and 10 keys
    attn = 4 * 3 * 4 * 2 * 15
    assert flops.tick_flops(M, [4, 9]) == \
        2 * 3 * 480 * 2 + attn + 2 * 2 * 8 * 10


def test_flash_attention_cost():
    f, b = flops.flash_attention_cost(M, 3)
    assert f == 4 * 4 * 2 * 6                   # 6 causal pairs
    assert b == 2 * 3 * 8 * 2 + 2 * 3 * 4 * 2 + 3 * 4 * 4


def test_paged_flash_prefill_cost():
    f, b = flops.paged_flash_prefill_cost(M, 3, 5)
    assert f == 4 * 4 * 2 * 15                  # 3 queries x 5 history keys
    assert b == 2 * 3 * 8 * 2 + 2 * 5 * 4 * 2 + 3 * 4 * 4


def test_paged_flash_decode_cost():
    f, b = flops.paged_flash_decode_cost(M, [4, 9])
    assert f == 4 * 4 * 2 * 15
    # live K,V read (15 tokens), new K,V written (2), q in and o out (2)
    assert b == 2 * 15 * 4 * 2 + 2 * 2 * 4 * 2 + 2 * 2 * 8 * 2


@pytest.mark.parametrize("f,b,bound", [(197e12, 1.0, 1.0),
                                       (1.0, 819e9, 1.0)])
def test_roofline_takes_the_larger_bound(f, b, bound):
    assert flops.roofline_seconds(f, b, {"flops": 197e12,
                                         "hbm_bw": 819e9}) == bound
