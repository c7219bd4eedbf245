"""Row-softmax Pallas kernel — the tuned ``sfm`` workload's TPU lowering.

One grid step owns a block of rows; the full row lives in VMEM so the
max/exp/sum/divide chain fuses into a single pass (the four blocks of the
``sfm`` PrimFunc collapse into one kernel body).  The row-block size is
the MetaSchedule-tunable parameter, extracted from the tuned trace by
:mod:`repro.backends.pallas_backend`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_metadata

DEFAULT_ROW_BLOCK = 128


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def row_softmax(
    x: jnp.ndarray,
    *,
    block_rows: int = DEFAULT_ROW_BLOCK,
    interpret: bool,
    task: str = "",
) -> jnp.ndarray:
    """Numerically-stable softmax over the last axis of a 2-D array."""
    M, N = x.shape
    bm = min(block_rows, M)
    assert M % bm == 0, f"row block {block_rows} must divide {M}"
    return pl.pallas_call(
        _softmax_kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        name="row_softmax",
        metadata=kernel_metadata(task, (bm,), x.dtype),
    )(x)
