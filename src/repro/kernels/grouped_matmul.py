"""Grouped matmul Pallas kernel: the rows of ``x`` sorted by group, each
group's rows times its own weight — the dropless expert layer's up/gate and
down projections.

After the megablox design (``jax.experimental.pallas.ops.tpu.megablox``):
the group sizes are runtime values, so the kernel's grid walks a list of
(group, row tile) visits computed from them on the device and handed to the
kernel as scalar-prefetched operands: the groups' row offsets, the group
of each visit and the row tile of each visit.  A row tile that straddles a
group boundary is visited once per group it holds; each visit stores only
its own group's rows (a mask on the row index), and the visits of one tile
are consecutive, so its output block stays in VMEM until all of its rows
are written.  Empty groups take no visit.  The weight block of a visit is
``w[group, k tile, n tile]``; the float32 accumulator persists across the
sequential k dimension, as in :mod:`repro.kernels.matmul`.

The block sizes (bm, bn, bk) are the tuned parameters; their VMEM
footprint is that of the dense kernel (:func:`matmul.block_vmem_bytes`),
and the grid has at most ``m / bm + groups - 1`` visits per (n, k) tile.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_metadata


def group_visits(group_sizes: jnp.ndarray, m: int, bm: int):
    """(row offsets (g + 1,), group of each visit, row tile of each visit,
    number of visits) for groups of ``group_sizes`` rows, in order, over
    ``m`` rows cut into tiles of ``bm``.  The visit lists are
    ``m / bm + g - 1`` long; entries past the number of visits are unused."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offsets[:-1] // bm
    touched = jnp.where(sizes > 0, (ends + bm - 1) // bm - first, 0)
    n = m // bm + g - 1  # the most visits g groups of m rows can need
    gids = jnp.repeat(
        jnp.arange(g, dtype=jnp.int32), touched, total_repeat_length=n
    )
    before = jnp.cumsum(touched) - touched  # visits of earlier groups
    mids = first[gids] + jnp.arange(n, dtype=jnp.int32) - before[gids]
    count = jnp.sum(touched)
    # entries past the last visit repeat it, so that no index points
    # outside the operands even where the grid is walked in full
    last = jnp.maximum(count - 1, 0)
    live = jnp.arange(n) < count
    gids = jnp.where(live, gids, gids[last])
    mids = jnp.where(live, mids, mids[last])
    return offsets, gids, mids.astype(jnp.int32), count


def _kernel(offsets, gids, mids, x_ref, w_ref, o_ref, acc_ref, *, nk, bm):
    t, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(kk == nk - 1)
    def _store():
        e = gids[t]
        rows = mids[t] * bm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0
        )
        mine = (rows >= offsets[e]) & (rows < offsets[e + 1])
        o_ref[...] = jnp.where(
            mine, acc_ref[...], o_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


def grouped_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    group_sizes: jnp.ndarray,
    *,
    block_sizes: Tuple[int, int, int],
    interpret: bool,
    task: str = "",
) -> jnp.ndarray:
    """y[rows of group e] = x[rows of group e] @ w[e]; x: (M, K) with its
    rows sorted by group, w: (G, K, N), group_sizes: (G,) int32 summing to
    M.  Output (M, N) in x's dtype, accumulated in float32.

    ``block_sizes`` is (bm, bn, bk), each dividing its dimension.
    ``task`` is the workload key of the tuned record the blocks came from
    (:func:`kernel_metadata`).
    """
    M, K = x.shape
    G, K2, N = w.shape
    assert K == K2 and group_sizes.shape == (G,), (x.shape, w.shape)
    bm, bn, bk = block_sizes
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"blocks {block_sizes} must divide {(M, N, K)}"
    )
    nk = K // bk
    offsets, gids, mids, count = group_visits(group_sizes, M, bm)
    grid = (N // bn, count, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, bm=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda n, t, k, o, g, r: (r[t], k)),
                pl.BlockSpec(
                    (None, bk, bn), lambda n, t, k, o, g, r: (g[t], k, n)
                ),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda n, t, k, o, g, r: (r[t], n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
        name="grouped_dense",
        metadata=kernel_metadata(task, (bm, bn, bk), x.dtype),
    )(offsets, gids, mids, x, w)
