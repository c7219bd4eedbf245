"""Operations and bytes, computed from shapes, for kernels (the model
steps' counts are each reference module's, under ``refs/``).

Every count is what the algorithm needs, in the model's dtype (bf16, two
bytes an element): a kernel that reads float32 copies, or computes the
masked half of a causal square, does more than this and so reads further
from its roofline, never closer.  Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

BF16 = 2


def causal_pairs(s: int, t: int) -> int:
    """(query, key) pairs a causal mask admits when the ``s`` queries are
    the last ``s`` positions of a ``t``-long sequence."""
    return s * (t - s) + s * (s + 1) // 2


def dense(m: int, n: int, k: int) -> Tuple[int, int]:
    return 2 * m * n * k, (m * k + k * n + m * n) * BF16


def batch_matmul(b: int, m: int, n: int, k: int) -> Tuple[int, int]:
    return 2 * b * m * n * k, b * (m * k + k * n + m * n) * BF16


def attention(bh: int, bkvh: int, s: int, d: int) -> Tuple[int, int]:
    """Causal self-attention: ``bh`` query heads over ``bkvh`` kv heads."""
    flops = 4 * bh * causal_pairs(s, s) * d
    return flops, (2 * bh * s * d + 2 * bkvh * s * d) * BF16


def attention_decode(bkvh: int, g: int, t: int, d: int) -> Tuple[int, int]:
    """One query token per head against a ``t``-long cache (every cache
    position is read; the mask is data)."""
    return 4 * bkvh * g * t * d, (2 * bkvh * g * d + 2 * bkvh * t * d) * BF16


def kernel_work(
    result: Sequence[int], operands: Sequence[Sequence[int]]
) -> Optional[Tuple[str, int, int]]:
    """(kind, flops, bytes) of one kernel call from its result and operand
    shapes, or None for a shape pattern this table does not know."""
    r = tuple(result)
    ops = [tuple(o) for o in operands]
    ranks = [len(o) for o in ops]
    if len(r) == 2 and len(ops) >= 2 and ranks[:2] == [2, 2]:
        (m, k), (k2, n) = ops[0], ops[1]
        if k == k2 and r == (m, n):
            return ("dense",) + dense(m, n, k)
    if len(r) == 3 and len(ops) == 2 and ranks == [3, 3]:
        (b, m, k), (b2, k2, n) = ops
        if b == b2 and k == k2 and r == (b, m, n):
            return ("batch_matmul",) + batch_matmul(b, m, n, k)
    if len(r) == 3 and len(ops) == 3 and ranks == [3, 3, 3]:
        (bh, s, d), kk, vv = ops
        if kk == vv and kk[1:] == (s, d) and r == (bh, s, d) and bh % kk[0] == 0:
            return ("attention",) + attention(bh, kk[0], s, d)
    if len(r) == 3 and len(ops) == 4 and ranks[:3] == [3, 3, 3]:
        (bkvh, g, d), kk, vv = ops[:3]
        if kk == vv and kk[0] == bkvh and kk[2] == d and r == (bkvh, g, d):
            return ("attention_decode",) + attention_decode(bkvh, g, kk[1], d)
    return None


def ideal_s(flops: float, nbytes: float, peak: Dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])

