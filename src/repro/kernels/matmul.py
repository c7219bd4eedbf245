"""Tunable MXU matmul Pallas kernel — the Use-MXU tensorize target.

Block shapes (bm, bn, bk) are the MetaSchedule-tuned parameters: the
pallas backend extracts them from a Use-MXU trace and instantiates this
kernel (DESIGN.md §4).  HBM→VMEM staging is expressed with BlockSpecs (the
TPU analogue of the paper's ``cache_read shared.dyn``); the fp32 VMEM
accumulator persists across the sequential k grid dimension; the epilogue
(bias / relu / gelu / silu / gemma softcap) is fused at the final k step —
the TPU counterpart of the paper's reverse-compute-at epilogue fusion.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_metadata
from .ref import apply_epilogue

DEFAULT_BLOCKS = (128, 128, 128)  # MXU-native tiles


def _matmul_kernel(
    x_ref, w_ref, b_ref, o_ref, acc_ref, *, nk: int, epilogue: str, softcap: float
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        acc = acc_ref[...]
        bias = b_ref[...] if b_ref is not None else None
        acc = apply_epilogue(acc, epilogue, bias, softcap)
        o_ref[...] = acc.astype(o_ref.dtype)


def matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    *,
    epilogue: str = "none",
    softcap: float = 30.0,
    block_sizes: Tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool,
    task: str = "",
) -> jnp.ndarray:
    """y = epilogue(x @ w + bias); x: (M, K), w: (K, N).

    ``interpret=True`` runs the kernel body in the Pallas interpreter
    (any platform); ``interpret=False`` is the Mosaic lowering (TPU).
    ``task`` is the workload key of the tuned record the blocks came
    from, carried in the kernel's metadata (:func:`kernel_metadata`).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bn, bk = block_sizes
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"blocks {block_sizes} must divide {(M, N, K)}"
    )
    nk = K // bk
    kernel = functools.partial(
        _matmul_kernel, nk=nk, epilogue=epilogue, softcap=softcap
    )
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if bias is not None:
        # a (1, bn) block of a (1, N) row: Mosaic's layout for a 1-D
        # operand does not match XLA's
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(bias.reshape(1, N))
        body = kernel
    else:
        body = lambda xr, wr, orf, acc: kernel(xr, wr, None, orf, acc)
    return pl.pallas_call(
        body,
        grid=(M // bm, N // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
        name="dense",
        metadata=kernel_metadata(task, (bm, bn, bk), x.dtype),
    )(*args)


def _bmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def batch_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    block_sizes: Tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool,
    task: str = "",
) -> jnp.ndarray:
    """y[b] = x[b] @ w[b]; x: (B, M, K), w: (B, K, N).

    Batch rides a leading parallel grid dimension; per-batch tiling is
    identical to :func:`matmul` (fp32 VMEM accumulator across the
    sequential k dimension).  The attention score/value contractions and
    MoE expert FFNs lower here.
    """
    B, M, K = x.shape
    B2, K2, N = w.shape
    assert B == B2 and K == K2, (x.shape, w.shape)
    bm, bn, bk = block_sizes
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"blocks {block_sizes} must divide {(M, N, K)}"
    )
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_bmm_kernel, nk=nk),
        grid=(B, M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, j, k: (b, i, k)),
            pl.BlockSpec((1, bk, bn), lambda b, i, j, k: (b, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
        name="batch_matmul",
        metadata=kernel_metadata(task, (bm, bn, bk), x.dtype),
    )(x, w)
