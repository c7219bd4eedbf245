"""Operations and bytes from shapes against hand counts at small sizes."""

import pytest
from benchlib import CHIP  # noqa: F401

import work


def test_causal_pairs():
    assert work.causal_pairs(4, 4) == 10  # 1 + 2 + 3 + 4
    assert work.causal_pairs(1, 7) == 7  # one query at the end sees all
    assert work.causal_pairs(2, 5) == 4 + 5


@pytest.mark.parametrize(
    "result, operands, want",
    [
        ((8, 16), [(8, 4), (4, 16)], ("dense", 2 * 8 * 16 * 4, (32 + 64 + 128) * 2)),
        ((8, 16), [(8, 4), (4, 16), (1, 16)], ("dense", 1024, 448)),
        ((3, 8, 16), [(3, 8, 4), (3, 4, 16)],
         ("batch_matmul", 3 * 1024, 3 * 224 * 2)),
        # 6 query heads over 2 kv heads, 4 positions, head 8: causal pairs 10
        ((6, 4, 8), [(6, 4, 8), (2, 4, 8), (2, 4, 8)],
         ("attention", 4 * 6 * 10 * 8, (2 * 6 * 32 + 2 * 2 * 32) * 2)),
        # decode: 2 kv-head rows of 3 grouped queries against 16 positions
        ((2, 3, 8), [(2, 3, 8), (2, 16, 8), (2, 16, 8), (1, 1, 16)],
         ("attention_decode", 4 * 2 * 3 * 16 * 8, (2 * 2 * 3 * 8 + 2 * 2 * 16 * 8) * 2)),
    ],
)
def test_kernel_work_hand_counts(result, operands, want):
    assert work.kernel_work(result, operands) == want


def test_unknown_kernel_is_none():
    assert work.kernel_work((5,), [(5,), (5,)]) is None


SMALL = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 32}


def test_forward_flops_hand_count():
    z = work.sizes(SMALL)
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 = 64+32+32+64+384
    assert work.layer_params(z) == 576
    tokens = 3 * 4
    mm = 2 * (2 * 576 + 32 * 8) * tokens
    attn = 2 * 4 * 3 * 2 * 10 * 4  # L * 4 * B * H * pairs(4, 4) * hd
    assert work.forward_flops(z, 3, 4) == mm + attn
    assert work.weight_bytes(z) == (2 * 576 + 256) * 2


def test_serve_tick_counts_real_tokens_and_live_cache():
    z = work.sizes(SMALL)
    flops, nbytes = work.serve_tick(z, [(1, 10), (3, 3)])
    per_tok = 2 * 2 * 576
    want = per_tok * 4 + 2 * 2 * 32 * 8
    want += 2 * 4 * 2 * (work.causal_pairs(1, 10) + work.causal_pairs(3, 3)) * 4
    assert flops == want
    kv_tok = 2 * 2 * 1 * 4 * 2
    assert nbytes == work.weight_bytes(z) + 13 * kv_tok


def test_ideal_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.ideal_s(1000, 10, peak) == 10.0
    assert work.ideal_s(100, 1000, peak) == 100.0
