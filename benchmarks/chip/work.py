"""Operations and bytes, computed from shapes, for kernels and model steps.

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


# ---------------------------------------------------------------------------
# Model steps (dense decoder: attention + gated MLP, tied unembedding)
# ---------------------------------------------------------------------------


def sizes(conf: Dict) -> Dict[str, int]:
    """The shape keys of a configuration file, under short names."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {
        "L": conf["num_hidden_layers"],
        "D": d,
        "H": h,
        "KVH": conf["num_key_value_heads"],
        "hd": conf.get("head_dim") or d // h,
        "F": conf["intermediate_size"],
        "V": conf["vocab_size"],
    }


def layer_params(z: Dict[str, int]) -> int:
    """Matmul parameters of one layer (norm weights are not matmuls)."""
    attn = z["D"] * z["H"] * z["hd"] * 2 + z["D"] * z["KVH"] * z["hd"] * 2
    return attn + 3 * z["D"] * z["F"]


def weight_bytes(z: Dict[str, int]) -> int:
    return (z["L"] * layer_params(z) + z["V"] * z["D"]) * BF16


def forward_flops(z: Dict[str, int], batch: int, seq: int) -> int:
    """One causal forward over ``batch`` sequences of ``seq`` tokens,
    logits at every position."""
    tokens = batch * seq
    mm = 2 * (z["L"] * layer_params(z) + z["V"] * z["D"]) * tokens
    attn = z["L"] * 4 * batch * z["H"] * causal_pairs(seq, seq) * z["hd"]
    return mm + attn


def serve_tick(
    z: Dict[str, int], lanes: Sequence[Tuple[int, int]]
) -> Tuple[int, int]:
    """(flops, bytes) one serving tick needs for its real tokens.

    ``lanes`` holds ``(new_tokens, context)`` for each lane that carries
    work: ``new_tokens`` enter at the end of a ``context``-long sequence
    (their own positions included), and one row of logits is taken per
    lane.  Bytes: every weight once, plus the live KV cache each lane
    reads and the KV it writes."""
    flops, kv = 0, 0
    per_tok = 2 * z["L"] * layer_params(z)
    kv_tok = z["L"] * 2 * z["KVH"] * z["hd"] * BF16
    for n, ctx in lanes:
        flops += per_tok * n + 2 * z["V"] * z["D"]
        flops += z["L"] * 4 * z["H"] * causal_pairs(n, ctx) * z["hd"]
        kv += ctx * kv_tok
    return flops, weight_bytes(z) + kv
