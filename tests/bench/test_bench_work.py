"""Kernels' operations and bytes from shapes against hand counts at small
sizes (the model steps' counts are tested in test_bench_refs.py)."""

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


def test_ideal_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.ideal_s(1000, 10, peak) == 10.0
    assert work.ideal_s(100, 1000, peak) == 100.0
