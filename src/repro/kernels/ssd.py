"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

Implements the chunked SSD algorithm (Dao & Gu, arXiv:2405.21060): the
sequence is split into chunks; within a chunk the recurrence is computed as
a decay-masked attention-like contraction (MXU-friendly), across chunks a
small (N × P) state is carried in VMEM scratch through the sequential chunk
grid dimension.  Chunk length is MetaSchedule-tunable.

Layout: one (batch, head) pair per outer grid step; state persists across
the inner (chunk) grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_metadata


def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, y_ref, h_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)  # (L, P)
    la = la_ref[0].astype(jnp.float32)  # (L,)
    B = b_ref[0].astype(jnp.float32)  # (L, N)
    C = c_ref[0].astype(jnp.float32)  # (L, N)

    cum = jnp.cumsum(la)  # (L,)
    # intra-chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) x_j
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dec = jnp.exp(cum[:, None] - cum[None, :])
    dec = jnp.where(i >= j, dec, 0.0)
    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32) * dec
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(cum_i) * C_i . h_prev
    h_prev = h_ref[...]  # (N, P)
    y = y + jnp.exp(cum)[:, None] * jnp.dot(
        C, h_prev, preferred_element_type=jnp.float32
    )

    # state update: h = exp(cum_L) h_prev + sum_j exp(cum_L - cum_j) B_j x_j
    total = cum[-1]
    w = jnp.exp(total - cum)  # (L,)
    h_new = jnp.exp(total) * h_prev + jnp.dot(
        (B * w[:, None]).T, x, preferred_element_type=jnp.float32
    )
    h_ref[...] = h_new
    y_ref[0] = y.astype(y_ref.dtype)


def ssd(
    x: jnp.ndarray,
    log_a: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    *,
    chunk: int = 64,
    interpret: bool,
    task: str = "",
) -> jnp.ndarray:
    """x: (batch, S, H, P); log_a: (batch, S, H); B, C: (batch, S, N)."""
    batch, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    # fold (batch, head) into the leading grid dim; B/C shared across heads
    xb = x.transpose(0, 2, 1, 3).reshape(batch * H, S, P)
    lab = log_a.transpose(0, 2, 1).reshape(batch * H, S)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)

    def xmap(bh, c):
        return (bh, c, 0)

    def lamap(bh, c):
        return (bh, c)

    def bcmap(bh, c):
        return (bh // H, c, 0)

    y = pl.pallas_call(
        kernel,
        grid=(batch * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), xmap),
            pl.BlockSpec((1, chunk), lamap),
            pl.BlockSpec((1, chunk, N), bcmap),
            pl.BlockSpec((1, chunk, N), bcmap),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), xmap),
        out_shape=jax.ShapeDtypeStruct((batch * H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
        name="ssd",
        metadata=kernel_metadata(task, (chunk,), x.dtype),
    )(xb, lab, B, C)
    return y.reshape(batch, H, S, P).transpose(0, 2, 1, 3)
