"""Blocked (flash) attention Pallas kernel.

Online-softmax attention with BlockSpec-tiled Q/K/V staging, supporting:
  * causal masking,
  * sliding-window (local) masking — gemma-2 local layers / hymba,
  * gemma-2 logit soft-capping,
  * GQA via BlockSpec index maps (kv head = q head // group) — no
    materialized K/V repetition.

The kv grid dimension is sequential; running (m, l, acc) statistics live in
VMEM scratch.  Block sizes (bq, bkv) are MetaSchedule-tunable.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_metadata

NEG_INF = -1e30


# Mosaic accepts a block whose last dim is a multiple of LANE and whose
# second-to-last is a multiple of SUBLANE, or either one equal to the
# array's full extent
SUBLANE, LANE = 8, 128


def best_divisor(n: int, target: int, align: int) -> int:
    """Divisor of ``n`` nearest to ``target`` among the multiples of
    ``align`` and ``n`` itself (Pallas needs exact tiling; Mosaic needs
    the alignment)."""
    best, bd = n, abs(n - target)
    d = 1
    while d * d <= n:
        if n % d == 0:
            for c in (d, n // d):
                if c % align == 0 and abs(c - target) < bd:
                    best, bd = c, abs(c - target)
        d += 1
    return best


def _attn_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    nkv: int,
    bq: int,
    bkv: int,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bkv, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), dtype=bool)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == nkv - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool,
    task: str = "",
) -> jnp.ndarray:
    """q: (B, H, S, D); k, v: (B, KVH, S, D); returns (B, H, S, D)."""
    B, H, S, D = q.shape
    KVH = k.shape[1]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / (D**0.5)
    # snap requested blocks to aligned divisors of S: BlockSpecs need exact
    # tiling, and tuned (block_q, block_kv) may come from a trace sampled on a
    # different-shaped relative of this call
    bq = best_divisor(S, min(block_q, S), SUBLANE)
    bkv = best_divisor(S, min(block_kv, S), SUBLANE)
    nq, nkv = S // bq, S // bkv
    kernel = functools.partial(
        _attn_kernel,
        nkv=nkv,
        bq=bq,
        bkv=bkv,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
    )
    grid = (B * H, 1, nq, nkv)  # (batch*head, unit, q blocks, kv blocks)

    def qmap(bh, _, qi, ki):
        return (bh, qi, 0)

    def kvmap(bh, _, qi, ki):
        # GQA: q head bh%H maps to kv head (bh%H)//G
        b = bh // H
        h = bh % H
        return (b * KVH + h // G, ki, 0)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), qmap),
            pl.BlockSpec((1, bkv, D), kvmap),
            pl.BlockSpec((1, bkv, D), kvmap),
        ],
        out_specs=pl.BlockSpec((1, bq, D), qmap),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
        name="flash_attention",
        metadata=kernel_metadata(task, (bq, bkv), q.dtype),
    )(
        q.reshape(B * H, S, D),
        k.reshape(B * KVH, S, D),
        v.reshape(B * KVH, S, D),
    )
    return out.reshape(B, H, S, D)


def _decode_kernel(
    q_ref,
    k_ref,
    v_ref,
    b_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    nkv: int,
    scale: float,
    softcap: Optional[float],
):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (g, d)
    k = k_ref[0]  # (bkv, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    # the mask is pure data: an additive (1, bkv) bias row — 0 attendable,
    # -1e30 not — computed by the caller from the per-slot lengths
    s = s + b_ref[0]

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == nkv - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def decode_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_kv: int = 128,
    interpret: bool,
    task: str = "",
) -> jnp.ndarray:
    """Single-token decode attention over a fixed-shape KV cache.

    q: (B, KVH, G, D) — one query token per sequence, GQA-grouped;
    k, v: (B, KVH, T, D) — the full cache; bias: (B, T) additive mask
    (0 attendable / -1e30 masked), shared across heads.  Returns
    (B, KVH, G, D).  Only the kv axis is blocked (``block_kv``); the G
    query rows of a kv head ride in one tile — decode's whole q extent.
    """
    B, KVH, G, D = q.shape
    T = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D**0.5)
    # bkv is the lane dim of the bias block
    bkv = best_divisor(T, min(block_kv, T), LANE)
    nkv = T // bkv
    kernel = functools.partial(
        _decode_kernel, nkv=nkv, scale=scale, softcap=softcap
    )
    grid = (B * KVH, nkv)  # (batch*kv head, kv blocks — sequential)

    def qmap(bh, ki):
        return (bh, 0, 0)

    def kvmap(bh, ki):
        return (bh, ki, 0)

    def bmap(bh, ki):
        return (bh // KVH, 0, ki)  # bias is per sequence, shared across heads

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, D), qmap),
            pl.BlockSpec((1, bkv, D), kvmap),
            pl.BlockSpec((1, bkv, D), kvmap),
            pl.BlockSpec((1, 1, bkv), bmap),
        ],
        out_specs=pl.BlockSpec((1, G, D), qmap),
        out_shape=jax.ShapeDtypeStruct((B * KVH, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
        name="flash_decode",
        metadata=kernel_metadata(task, (bkv,), q.dtype),
    )(
        q.reshape(B * KVH, G, D),
        k.reshape(B * KVH, T, D),
        v.reshape(B * KVH, T, D),
        # (B, 1, T): a (1, bkv) block over the last two dims is legal for
        # any batch size, where a (1, bkv) block over (B, T) is not
        bias.reshape(B, 1, T),
    )
    return out.reshape(B, KVH, G, D)


def _paged_decode_kernel(
    table_ref,  # scalar-prefetch: (B, P) physical page ids
    q_ref,
    k_ref,
    v_ref,
    b_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    npages: int,
    scale: float,
    softcap: Optional[float],
):
    del table_ref  # consumed by the BlockSpec index maps
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (g, d)
    k = k_ref[0, 0]  # (ps, d) — one physical page
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    # mask as data, like _decode_kernel: the (1, ps) bias row covers both
    # the per-slot length and any page the slot never wrote
    s = s + b_ref[0, 0]

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == npages - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def paged_decode_flash_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool,
    task: str = "",
) -> jnp.ndarray:
    """Single-token decode attention reading straight through a page table.

    q: (B, KVH, G, D) — one query token per sequence, GQA-grouped;
    k_pool, v_pool: (n_pages, KVH, ps, D) — the shared page pools of a
    :class:`~repro.serving.kv.PagedKVArena` layer; page_table: (B, P)
    physical page ids (sentinel entries are clamped into the pool — the
    bias must mask their positions); bias: (B, P * ps) additive mask
    (0 attendable / -1e30 masked), shared across heads.  Returns
    (B, KVH, G, D), numerically identical to ``decode_flash_attention``
    over the gathered contiguous view.

    The page table rides in as a scalar-prefetch operand
    (``PrefetchScalarGridSpec``): the kv BlockSpec index maps read it to
    aim each sequential grid step's DMA at the slot's next physical page,
    so no gathered (B, KVH, T, D) copy of the cache is ever materialized.
    The kv grid axis is one page per step — pages *are* the kv blocks.
    """
    B, KVH, G, D = q.shape
    n_pages, _, ps, _ = k_pool.shape
    P = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / (D**0.5)
    # sentinel entries (== n_pages, one past the pool) index clamped —
    # their bias positions are already -1e30 by the caller's contract
    table = jnp.minimum(page_table.astype(jnp.int32), n_pages - 1)
    kernel = functools.partial(
        _paged_decode_kernel, npages=P, scale=scale, softcap=softcap
    )
    grid = (B * KVH, P)  # (batch*kv head, pages — sequential)

    def qmap(bh, ki, t):
        return (bh, 0, 0)

    def kvmap(bh, ki, t):
        return (t[bh // KVH, ki], bh % KVH, 0, 0)

    def bmap(bh, ki, t):
        return (bh // KVH, ki, 0, 0)  # per sequence, shared across heads

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, D), qmap),
            pl.BlockSpec((1, 1, ps, D), kvmap),
            pl.BlockSpec((1, 1, ps, D), kvmap),
            pl.BlockSpec((1, 1, 1, ps), bmap),
        ],
        out_specs=pl.BlockSpec((1, G, D), qmap),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KVH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
        name="paged_decode",
        metadata=kernel_metadata(task, (ps,), q.dtype),
    )(
        table,
        q.reshape(B * KVH, G, D),
        k_pool,
        v_pool,
        # (B, P, 1, ps): one page's bias row is a full-extent (1, ps) block
        bias.reshape(B, P, 1, ps),
    )
    return out.reshape(B, KVH, G, D)
