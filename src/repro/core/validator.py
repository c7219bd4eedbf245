"""Trace validation (paper §4, Figure 7 "validator").

Mutated traces can leave the support of the probabilistic program: decisions
out of range, splits that no longer multiply to the extent, compute-at
locations invalidated by structural changes, or resource blow-ups (the TPU
analogue of the paper's ``thread_extent`` limits is the VMEM tile
footprint).  Instead of constraining proposals conservatively, the search
proposes freely and this validator rejects out-of-support samples.

A schedule whose MXU-tensorized block lowers onto the Pallas matmul
kernel (``dense_*``, ``batch_matmul``) is held to that kernel's limits:
the grid of the block it will run and the VMEM that block's buffers
take.  Every other schedule is held to the caps below, which bound the
jnp backend's loop nest.  ``ValidationResult.kind`` names the limit a
rejection hit: ``replay``, ``iteration``, ``tile`` or ``vmem``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from .schedule import LoopNode, Schedule
from .tir import PrimFunc
from .trace import Trace

# resource limits of every schedule that does not lower onto the matmul
# kernel (TPU v5e-flavored; CPU measurement uses the same caps)
MAX_ITERATIONS = 1 << 21      # fori_loop trip-count guard (measurement cost)
MAX_TILE_ELEMS = 1 << 17      # joint tile (VREG/VMEM-resident working set)
MAX_VMEM_BYTES = 16 << 20     # staged operand tiles must fit VMEM


@dataclass
class ValidationResult:
    ok: bool
    schedule: Optional[Schedule]
    reason: str = ""
    iterations: int = 0
    tile_elems: int = 0
    vmem_bytes: int = 0

    @property
    def kind(self) -> str:
        """The limit a rejection hit (the reason's first word)."""
        return self.reason.split(" ", 1)[0].rstrip(":")


def validate_trace(func: PrimFunc, trace: Trace) -> ValidationResult:
    """Replay ``trace`` on a fresh schedule and check structural limits."""
    sch = Schedule(func, seed=None)
    try:
        trace.replay(sch)
    except Exception as e:  # out of support — any structural failure
        return ValidationResult(False, None, f"replay: {type(e).__name__}: {e}")
    return validate_schedule(sch)


def validate_schedule(sch: Schedule) -> ValidationResult:
    from ..backends.jnp_backend import _tile_suffix, estimate_iteration_count
    from ..backends.pallas_backend import (
        MAX_GRID_STEPS,
        find_tensorized_block,
        matmul_blocks,
        matmul_shape,
    )
    from ..kernels.matmul import vmem_budget

    shape = matmul_shape(sch.func)
    mxu = find_tensorized_block(sch)
    if shape is not None and (
        shape.groups or mxu.annotations.get("tensorize") == "mxu"
    ):
        _, blocks = matmul_blocks(sch, shape)
        steps = shape.grid_steps(blocks)
        tile = int(np.prod(blocks))
        if steps > MAX_GRID_STEPS:
            return ValidationResult(
                False, None, f"iteration grid {steps} > {MAX_GRID_STEPS}", steps
            )
        vmem, budget = shape.vmem_bytes(blocks), vmem_budget(shape.itemsize)
        if vmem > budget:
            return ValidationResult(
                False, None, f"vmem {vmem} > {budget}", steps, tile, vmem
            )
        return ValidationResult(True, sch, "", steps, tile, vmem)

    iters = estimate_iteration_count(sch)
    if iters > MAX_ITERATIONS:
        return ValidationResult(
            False, None, f"iteration count {iters} > {MAX_ITERATIONS}", iters
        )

    # per-block joint tile + VMEM footprint of staged tiles
    max_tile = 1
    vmem = 0

    def walk(nodes, path):
        nonlocal max_tile, vmem
        for n in nodes:
            if isinstance(n, LoopNode):
                walk(n.body, path + [n])
            else:
                tl = _tile_suffix(path, n)
                te = int(np.prod([l.extent for l in tl])) if tl else 1
                max_tile = max(max_tile, te)
                # staged (vmem-scope) buffers count fully; tiles count once
                if n.block.write.scope == "vmem":
                    vmem_local = n.block.write.nbytes
                else:
                    vmem_local = te * 4
                vmem += vmem_local

    walk(sch.root, [])
    if max_tile > MAX_TILE_ELEMS:
        return ValidationResult(
            False, None, f"tile {max_tile} > {MAX_TILE_ELEMS}", iters, max_tile
        )
    if vmem > MAX_VMEM_BYTES:
        return ValidationResult(
            False, None, f"vmem {vmem} > {MAX_VMEM_BYTES}", iters, max_tile, vmem
        )
    return ValidationResult(True, sch, "", iters, max_tile, vmem)


def first_valid_schedule(func: PrimFunc, space, seed_scan: int = 8):
    """The canonical *untuned* schedule of a workload: the first valid
    sample from ``space`` over a fixed seed scan.

    Single source of truth for the default-schedule baseline — the task
    scheduler's warm-start, the dispatch layer's ``mode="default"``
    context, and ``tune_workload``'s ``default_latency_s`` all call this,
    so "untuned" means the same program everywhere.  Returns a Schedule
    or None if the scan produces no valid sample.
    """
    for seed in range(seed_scan):
        v = validate_trace(func, space.generate(func, seed=seed).trace)
        if v.ok:
            return v.schedule
    return None
