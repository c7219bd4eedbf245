"""Lower tuned schedules onto the Pallas kernels.

The jnp backend measures schedules on CPU; *this* backend realizes the same
tuned schedule as a Pallas kernel: the (S2·S3) spatial tile extents and the
R1 reduce tile of the tensorized block become the Pallas ``BlockSpec``
shapes (bm, bn, bk) of :mod:`repro.kernels.matmul` (dense and batched), and
the row tile of a softmax schedule becomes the row-block of
:mod:`repro.kernels.softmax`.  Inlined/attached elementwise consumers
become the kernel's fused epilogue.  This is the concrete instantiation of
"MetaSchedule constructs the space, the backend carries the decisions to
hardware" (paper Fig 1 + Appendix A.6).

For a workload that lowers onto the matmul kernel the block is itself
the trace's decision: ``UseMXU`` draws (bm, bn, bk) from
:meth:`MatmulShape.legal_blocks` — aligned divisors of the problem whose
VMEM footprint fits the kernel's scoped VMEM — so such a sample lowers
unchanged.  Pallas needs exact tiling and Mosaic needs aligned blocks,
so every other sampled tile extent (and the matmul tiles of traces
recorded before the block was sampled) is *snapped* at lower time to the
nearest divisor of the problem shape that is a multiple of the TPU tile
(8 sublanes for a block's second-to-last dim, 128 lanes for its last) or
the whole dim.  Snapping is part of
the lowering's provenance: every ``lower_*`` path returns a meta dict with
both the sampled and the snapped blocks, which the measurement stack
persists into ``TuningRecord.meta`` and the dispatch layer surfaces on
``CompiledKernel.meta`` — the measured tile is never silently different
from the recorded one.

Workloads covered: ``dense_*`` (+fused epilogues), ``batch_matmul``,
``grouped_dense``, ``sfm``; everything else falls back to the jnp
structural lowering (see :class:`repro.backends.registry.PallasBackend`).
A fused flash-attention path
(:func:`repro.kernels.flash_attention.flash_attention`) is exposed to the
dispatch layer through ``PallasBackend.fused_attention``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from ..core.schedule import BlockNode, LoopNode, Schedule, iter_nodes
from ..core.tir import PrimFunc
from ..kernels.flash_attention import LANE, SUBLANE, best_divisor
from ..kernels.matmul import (
    DEFAULT_BLOCKS,
    block_vmem_bytes,
    sublane_tile,
    vmem_budget,
)
from ..kernels.softmax import DEFAULT_ROW_BLOCK

# PrimFunc names this backend can lower natively (dense_* covers every
# epilogue variant, incl. fused_dense which instantiates dense_bias_gelu;
# attention_* covers the causal/window/softcap variants)
_LOWERABLE_PREFIXES = ("dense_", "attention_")
_LOWERABLE_NAMES = ("batch_matmul", "sfm", "grouped_dense")


def supports(func: PrimFunc) -> bool:
    """True if this backend has a native Pallas lowering for ``func``."""
    return func.name in _LOWERABLE_NAMES or func.name.startswith(
        _LOWERABLE_PREFIXES
    )


def find_tensorized_block(sch: Schedule) -> Optional[BlockNode]:
    for n in iter_nodes(sch.root):
        if isinstance(n, BlockNode) and n.annotations.get("tensorize") == "mxu":
            return n
    # fall back: first reduce block
    for n in iter_nodes(sch.root):
        if isinstance(n, BlockNode) and n.block.reduce_axes:
            return n
    return None


def _per_axis_tile(sch: Schedule, bn_node: BlockNode) -> Dict[str, int]:
    """Tile extent per block axis (product of tile loops feeding it)."""
    from .jnp_backend import _tile_suffix

    blk = bn_node.block
    _, path = sch._find_block(blk.name)
    loops = [n for n in path if isinstance(n, LoopNode)]
    tile = _tile_suffix(loops, bn_node)
    per_axis: Dict[str, int] = {a.name: 1 for a in blk.axes}
    for ln in tile:
        for ax in blk.axes:
            if ln.var in bn_node.bindings[ax.name].vars():
                per_axis[ax.name] *= ln.extent
    return per_axis


def extract_matmul_blocks(sch: Schedule) -> Optional[Tuple[int, int, int]]:
    """(bm, bn, bk) from the tensorized block's tile structure."""
    bn_node = find_tensorized_block(sch)
    if bn_node is None:
        return None
    blk = bn_node.block
    if len(blk.spatial_axes) < 2 or len(blk.reduce_axes) < 1:
        return None
    per_axis = _per_axis_tile(sch, bn_node)
    if all(v == 1 for v in per_axis.values()):
        return None  # schedule carries no tile information
    s_axes = blk.spatial_axes
    r_axes = blk.reduce_axes
    # m = second-to-last spatial, n = last spatial, k = first reduce
    bm = per_axis[s_axes[-2].name]
    bn = per_axis[s_axes[-1].name]
    bk = per_axis[r_axes[0].name]
    return (max(bm, 1), max(bn, 1), max(bk, 1))


def extract_row_block(sch: Schedule) -> Optional[int]:
    """Row-tile extent (first spatial axis) for row-wise workloads (sfm):
    the max tile extent any block gives its leading spatial axis."""
    best = 0
    for n in iter_nodes(sch.root):
        if not isinstance(n, BlockNode) or not n.block.spatial_axes:
            continue
        per_axis = _per_axis_tile(sch, n)
        best = max(best, per_axis.get(n.block.spatial_axes[0].name, 1))
    return best if best > 1 else None


def snap_blocks(
    dims: Tuple[int, ...], blocks: Tuple[int, ...], aligns: Tuple[int, ...]
) -> Tuple[int, ...]:
    """Snap each sampled tile extent to the nearest divisor of its dim
    that is a multiple of its alignment, or to the whole dim."""
    return tuple(best_divisor(d, b, a) for d, b, a in zip(dims, blocks, aligns))


# Reject lowerings whose grid would explode: a 1-wide tile on a 128^3
# matmul means 2M grid steps — useless on the MXU and pathological in
# interpret mode.  Rejection surfaces as a failed build, which the search
# treats as an ordinary candidate rejection.
MAX_GRID_STEPS = 1 << 18


def _check_grid(steps: int, blocks) -> None:
    if steps > MAX_GRID_STEPS:
        raise ValueError(
            f"pallas grid of {steps} steps (blocks {tuple(blocks)}) exceeds "
            f"cap {MAX_GRID_STEPS}; schedule tiles too fine for this backend"
        )


# ---------------------------------------------------------------------------
# The matmul kernel's block space
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MatmulShape:
    """What a workload asks of :mod:`repro.kernels.matmul`: ``batch``
    products of (m, k) @ (k, n) in operands of ``itemsize`` bytes, with or
    without a bias row; or, with ``groups``, of
    :mod:`repro.kernels.grouped_matmul`: the m rows split into that many
    groups, each times its own (k, n) weight."""

    batch: int
    m: int
    n: int
    k: int
    itemsize: int
    bias: bool = False
    groups: int = 0

    @property
    def dims(self) -> Tuple[int, int, int]:
        return (self.m, self.n, self.k)

    @property
    def aligns(self) -> Tuple[int, int, int]:
        """bm is the sublane dim of the x block; bn and bk are lane dims."""
        return (sublane_tile(self.itemsize), LANE, LANE)

    def grid_steps(self, blocks) -> int:
        """Grid steps of the kernel (most, where the groups decide)."""
        bm, bn, bk = blocks
        rows = self.m // bm + max(self.groups - 1, 0)
        return self.batch * rows * (self.n // bn) * (self.k // bk)

    def vmem_bytes(self, blocks) -> int:
        return block_vmem_bytes(*blocks, self.itemsize, bias=self.bias)

    def legal_blocks(self) -> Tuple[Tuple[int, int, int], ...]:
        """Every (bm, bn, bk) the kernel takes unchanged: each an aligned
        divisor of its dim or the whole dim, whose buffers fit the
        kernel's VMEM budget and whose grid stays within
        ``MAX_GRID_STEPS``."""
        return _legal_blocks(self)


def _aligned_divisors(n: int, align: int) -> Tuple[int, ...]:
    return tuple(
        d for d in range(1, n + 1) if n % d == 0 and (d % align == 0 or d == n)
    )


@functools.lru_cache(maxsize=256)
def _legal_blocks(shape: MatmulShape) -> Tuple[Tuple[int, int, int], ...]:
    budget = vmem_budget(shape.itemsize)
    bms, bns, bks = (
        _aligned_divisors(d, a) for d, a in zip(shape.dims, shape.aligns)
    )
    return tuple(
        (bm, bn, bk)
        for bm in bms
        for bn in bns
        for bk in bks
        if shape.vmem_bytes((bm, bn, bk)) <= budget
        and shape.grid_steps((bm, bn, bk)) <= MAX_GRID_STEPS
    )


def matmul_shape(func: PrimFunc) -> Optional[MatmulShape]:
    """The matmul-kernel problem of a workload that lowers onto
    :mod:`repro.kernels.matmul` (``dense_*``, ``batch_matmul``) or
    :mod:`repro.kernels.grouped_matmul` (``grouped_dense``), else
    ``None``."""
    groups = 0
    if func.name.startswith("dense_"):
        (m, k), n, batch = func.inputs[0].shape, func.inputs[1].shape[1], 1
    elif func.name == "batch_matmul":
        (batch, m, k), n = func.inputs[0].shape, func.inputs[1].shape[2]
    elif func.name == "grouped_dense":
        (m, k), (groups, _, n), batch = (
            func.inputs[0].shape, func.inputs[1].shape, 1
        )
    else:
        return None
    return MatmulShape(
        batch, m, n, k, jnp.dtype(func.inputs[0].dtype).itemsize,
        bias=any(b.name == "bias" for b in func.inputs), groups=groups,
    )


def matmul_blocks(sch: Schedule, shape: MatmulShape):
    """(sampled, lowered) blocks of a matmul-kernel schedule: the tile the
    trace gives the tensorized block, and the block the kernel runs —
    the same for a block drawn from :meth:`MatmulShape.legal_blocks`."""
    sampled = extract_matmul_blocks(sch)
    blocks = snap_blocks(shape.dims, sampled or DEFAULT_BLOCKS, shape.aligns)
    return sampled, blocks


# ---------------------------------------------------------------------------
# Per-workload lowerings: schedule -> (fn, meta)
# ---------------------------------------------------------------------------


def lower_dense(
    sch: Schedule, *, interpret: bool, task: str = ""
) -> Tuple[Callable, Dict[str, Any]]:
    """Tuned dense (+fused epilogue) via the Pallas matmul kernel."""
    from ..kernels import matmul as mm

    func = sch.func
    shape = matmul_shape(func)
    sampled, blocks = matmul_blocks(sch, shape)
    _check_grid(shape.grid_steps(blocks), blocks)
    # epilogue from the ORIGINAL workload name (dense_<epilogue>)
    epilogue = "none"
    if func.name.startswith("dense_"):
        epilogue = func.name[len("dense_"):]
    meta = _block_meta("matmul", sampled, blocks)

    def fn(inputs: Dict):
        out = mm.matmul(
            inputs["X"],
            inputs["W"],
            inputs.get("bias"),
            epilogue=epilogue,
            block_sizes=blocks,
            interpret=interpret,
            task=task,
        )
        return {func.outputs[0].name: out}

    return fn, meta


def lower_batch_matmul(
    sch: Schedule, *, interpret: bool, task: str = ""
) -> Tuple[Callable, Dict[str, Any]]:
    """Tuned batched matmul via the Pallas bmm kernel (batch grid dim)."""
    from ..kernels import matmul as mm

    func = sch.func
    shape = matmul_shape(func)
    sampled, blocks = matmul_blocks(sch, shape)
    _check_grid(shape.grid_steps(blocks), blocks)
    meta = _block_meta("batch_matmul", sampled, blocks)

    def fn(inputs: Dict):
        out = mm.batch_matmul(
            inputs["A"], inputs["B"], block_sizes=blocks, interpret=interpret,
            task=task,
        )
        return {func.outputs[0].name: out}

    return fn, meta


def lower_grouped_dense(
    sch: Schedule, *, interpret: bool, task: str = ""
) -> Tuple[Callable, Dict[str, Any]]:
    """Tuned grouped matmul via the Pallas grouped kernel.  The group
    sizes are ``SIZES`` where the caller has them (dispatch), else the
    column sums of the workload's row-membership ``MASK`` (search)."""
    from ..kernels.grouped_matmul import grouped_matmul

    func = sch.func
    shape = matmul_shape(func)
    sampled, blocks = matmul_blocks(sch, shape)
    _check_grid(shape.grid_steps(blocks), blocks)
    meta = _block_meta("grouped_matmul", sampled, blocks)

    def fn(inputs: Dict):
        sizes = inputs.get("SIZES")
        if sizes is None:
            sizes = jnp.sum(inputs["MASK"].astype(jnp.int32), axis=0)
        out = grouped_matmul(
            inputs["X"], inputs["W"], sizes, block_sizes=blocks,
            interpret=interpret, task=task,
        )
        return {func.outputs[0].name: out}

    return fn, meta


def lower_sfm(
    sch: Schedule, *, interpret: bool, task: str = ""
) -> Tuple[Callable, Dict[str, Any]]:
    """Tuned row softmax via the Pallas online-softmax kernel."""
    from ..kernels import softmax as sm

    func = sch.func
    M = func.inputs[0].shape[0]
    sampled = extract_row_block(sch)
    (bm,) = snap_blocks((M,), (sampled or DEFAULT_ROW_BLOCK,), (SUBLANE,))
    meta = {
        "pallas_kernel": "row_softmax",
        "pallas_rows_sampled": sampled,
        "pallas_rows_snapped": bm,
    }

    def fn(inputs: Dict):
        out = sm.row_softmax(
            inputs["A"], block_rows=bm, interpret=interpret, task=task
        )
        return {func.outputs[0].name: out}

    return fn, meta


DEFAULT_ATTN_BLOCKS = (128, 128)  # MXU-native flash tiles (pre-tuning fixed)


def _parse_attention_name(name: str):
    """(causal, window, softcap) from ``attention_c{c}_w{w}[_t{cap}]``."""
    causal, window, softcap = True, None, None
    for part in name.split("_")[1:]:
        if part.startswith("c"):
            causal = bool(int(part[1:]))
        elif part.startswith("w"):
            window = int(part[1:]) or None
        elif part.startswith("t"):
            softcap = float(part[1:])
    return causal, window, softcap


def extract_attention_blocks(sch: Schedule) -> Optional[Tuple[int, int]]:
    """(block_q, block_kv) = the (i, j) tile extents of the scores block."""
    for n in iter_nodes(sch.root):
        if isinstance(n, BlockNode) and n.block.name == "scores":
            per_axis = _per_axis_tile(sch, n)
            bq, bkv = per_axis.get("i", 1), per_axis.get("j", 1)
            if bq == 1 and bkv == 1:
                return None  # schedule carries no tile information
            return (bq, bkv)
    return None


def lower_attention(
    sch: Schedule, *, interpret: bool, task: str = ""
) -> Tuple[Callable, Dict[str, Any]]:
    """Tuned fused attention via the Pallas flash kernel.

    The schedule's sampled (i, j) tiles of the ``scores`` block become the
    flash kernel's (block_q, block_kv), snapped to aligned divisors of
    the sequence length — the same sampled-vs-snapped provenance contract
    as the matmul tiles.
    """
    from ..kernels.flash_attention import flash_attention

    func = sch.func
    Q = func.inputs[0]
    b, kvh, g, s, d = Q.shape
    causal, window, softcap = _parse_attention_name(func.name)
    sampled = extract_attention_blocks(sch)
    blocks = snap_blocks(
        (s, s), sampled or DEFAULT_ATTN_BLOCKS, (SUBLANE, SUBLANE)
    )
    bq, bkv = blocks
    _check_grid(b * kvh * g * (s // bq) * (s // bkv), blocks)
    meta = _block_meta("flash_attention", sampled, blocks)

    def fn(inputs: Dict):
        q = inputs["Q"].reshape(b, kvh * g, s, d)
        out = flash_attention(
            q,
            inputs["K"],
            inputs["V"],
            causal=causal,
            window=window,
            softcap=softcap,
            block_q=bq,
            block_kv=bkv,
            interpret=interpret,
            task=task,
        )
        return {func.outputs[0].name: out.reshape(b, kvh, g, s, d)}

    return fn, meta


DEFAULT_DECODE_KV_BLOCK = 128  # pre-tuning fixed decode kv tile


def extract_decode_kv_block(sch: Schedule) -> Optional[int]:
    """block_kv = the j (kv) tile extent of the decode scores block."""
    for n in iter_nodes(sch.root):
        if isinstance(n, BlockNode) and n.block.name == "scores":
            per_axis = _per_axis_tile(sch, n)
            bkv = per_axis.get("j", 1)
            return bkv if bkv > 1 else None
    return None


def lower_attention_decode(
    sch: Schedule, *, interpret: bool, task: str = ""
) -> Tuple[Callable, Dict[str, Any]]:
    """Tuned single-token decode attention via the Pallas decode kernel.

    The decode workload has no query tiling (s_q = 1: the GQA group rides
    whole in one tile), so the only tunable block is the kv tile — the
    sampled ``j`` extent of the ``scores`` block, snapped to a 128-aligned
    divisor of the cache length (it is the lane dim of the bias block).  The dynamic mask arrives as the workload's BIAS
    input, passed straight through to the kernel.
    """
    from ..kernels.flash_attention import decode_flash_attention

    func = sch.func
    Q = func.inputs[0]
    b, kvh, g, d = Q.shape
    t = func.inputs[1].shape[2]
    softcap = None
    for part in func.name.split("_"):
        if part.startswith("t") and part != "t":
            try:
                softcap = float(part[1:])
            except ValueError:
                pass
    sampled = extract_decode_kv_block(sch)
    (bkv,) = snap_blocks((t,), (sampled or DEFAULT_DECODE_KV_BLOCK,), (LANE,))
    _check_grid(b * kvh * (t // bkv), (bkv,))
    meta = _block_meta(
        "decode_flash_attention",
        None if sampled is None else (sampled,),
        (bkv,),
    )

    def fn(inputs: Dict):
        out = decode_flash_attention(
            inputs["Q"],
            inputs["K"],
            inputs["V"],
            inputs["BIAS"],
            softcap=softcap,
            block_kv=bkv,
            interpret=interpret,
            task=task,
        )
        return {func.outputs[0].name: out}

    return fn, meta


def _block_meta(kernel: str, sampled, snapped) -> Dict[str, Any]:
    meta: Dict[str, Any] = {
        "pallas_kernel": kernel,
        "pallas_blocks_snapped": list(snapped),
    }
    if sampled is not None:
        meta["pallas_blocks_sampled"] = list(sampled)
        if tuple(sampled) != tuple(snapped):
            meta["pallas_blocks_adjusted"] = True
    else:
        meta["pallas_blocks_source"] = "default"
    return meta


def lower_to_pallas(
    sch: Schedule, *, interpret: bool, task: str = ""
) -> Tuple[Callable, Dict[str, Any]]:
    """Dispatch a supported schedule to its Pallas lowering.

    Returns ``(fn, meta)`` where ``fn`` is ``callable(dict) -> dict`` and
    ``meta`` records the kernel used plus sampled/snapped tile provenance.
    ``task`` (the workload key) goes into the kernel's metadata, so the
    candidate the search timed and the kernel dispatch serves carry the
    same identity in a profiler trace.  Raises ``ValueError`` for
    unsupported workloads (check ``supports``).
    """
    name = sch.func.name
    kw = dict(interpret=interpret, task=task)
    if name.startswith("dense_"):
        return lower_dense(sch, **kw)
    if name.startswith("attention_decode"):
        # must route before the generic attention_ prefix: the prefill
        # flash lowering assumes a 5-D square-sequence Q
        return lower_attention_decode(sch, **kw)
    if name.startswith("attention_"):
        return lower_attention(sch, **kw)
    if name == "batch_matmul":
        return lower_batch_matmul(sch, **kw)
    if name == "grouped_dense":
        return lower_grouped_dense(sch, **kw)
    if name == "sfm":
        return lower_sfm(sch, **kw)
    raise ValueError(f"no Pallas lowering for workload {name!r}")

