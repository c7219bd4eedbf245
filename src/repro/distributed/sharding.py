"""Sharding rules: logical axes → mesh axes with divisibility fallback.

Parallelism layout (DESIGN.md §5):

* ``model`` mesh axis — tensor parallel (attention heads / FFN hidden /
  vocab) and expert parallel (MoE experts);
* ``data`` (+ ``pod``) — data parallel batch AND fully-sharded (ZeRO-3)
  parameters/optimizer state;
* sequence dim of long activations / KV caches falls back across axes by
  divisibility (context parallelism for the 512k decode cells).

Every rule is a *fallback chain*: the first candidate whose axis sizes
divide the dimension wins, else the dim is replicated.  This is also the
elastic-rescale story — specs are recomputed for whatever mesh exists, so
a checkpoint can restore onto a different topology.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Mesh context
# ---------------------------------------------------------------------------

_CTX = threading.local()


def set_mesh(mesh: Optional[Mesh]) -> None:
    _CTX.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return getattr(_CTX, "mesh", None)


class use_mesh:
    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        self._prev = get_mesh()
        set_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *a):
        set_mesh(self._prev)


def _axis_size(mesh: Mesh, axes) -> int:
    """Product of the named axes' sizes; axes the mesh lacks count as 1
    (a data-only mesh has no "model" axis — treat it as unsplit)."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a] if a in mesh.axis_names else 1
    return out


def _mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def _dp_axes(mesh: Mesh):
    """Data-parallel axes: ('pod', 'data') when multi-pod, else ('data',)."""
    names = _mesh_axes(mesh)
    return tuple(a for a in ("pod", "data") if a in names)


# ---------------------------------------------------------------------------
# Fallback-chain resolution
# ---------------------------------------------------------------------------


def _resolve_dim(mesh: Mesh, size: int, chain: Sequence) -> Optional[Any]:
    """First candidate in the chain whose mesh size divides ``size``.

    Candidates naming an axis the mesh doesn't have are skipped (not
    treated as size-1 matches): a PartitionSpec may only reference real
    mesh axes, so e.g. "model" is simply not an option on a data-only
    mesh."""
    names = set(mesh.axis_names)
    for cand in chain:
        if cand is None:
            return None
        axes = (cand,) if isinstance(cand, str) else tuple(cand)
        if any(a not in names for a in axes):
            continue
        if size % _axis_size(mesh, cand) == 0:
            return cand
    return None


# ---------------------------------------------------------------------------
# Strategy knobs (the §Perf hillclimb levers; see EXPERIMENTS.md §Perf)
# ---------------------------------------------------------------------------

import os as _os

STRATEGY: Dict[str, Any] = {
    # shard the inter-layer residual's sequence dim on "model" (Megatron-SP).
    # Saves saved-activation memory but forces a seq<->heads re-layout every
    # layer; measured collective-dominant on this topology -> default OFF.
    # measured (EXPERIMENTS.md §Perf iter 1): ON is 6x better on collective
    # bytes and 3.4x on flops (OFF causes replicated recompute) -> default ON
    "sp_residual": _os.environ.get("REPRO_SP_RESIDUAL", "1") == "1",
    # when heads don't divide the model axis, fall back to sharding head_dim
    # (contracts over a sharded dim -> all-reduce per attention chunk) or
    # replicate the activation and let weight sharding drive (iter 2)
    "act_head_dim_fallback": _os.environ.get("REPRO_ACT_HD", "0") == "1",  # iter 2: OFF is 3.9x better
    # explicitly constrain q/k/v activations (True) or let GSPMD propagate
    # from the weight shardings (False).  Iter 3: explicit constraints force
    # full q/k/v(+grad) gathers when heads don't divide the model axis.
    # "auto" (iter 7): constrain q/k/v iff the heads dim divides the model
    # axis — explicit head sharding wins there (stablelm/whisper regressed
    # 0.6-0.7x with blanket OFF), GSPMD propagation wins otherwise.
    "constrain_attn_acts": _os.environ.get("REPRO_CONSTRAIN_ATTN", "auto"),
}


def set_strategy(**kwargs) -> None:
    STRATEGY.update(kwargs)


# activation kinds -> per-dim fallback chains (built lazily per mesh)
def _act_chains(mesh: Mesh) -> Dict[str, List]:
    dp = _dp_axes(mesh)
    seq_chain = ["model", None] if STRATEGY["sp_residual"] else [None]
    return {
        # (B, S, D): batch on dp; seq optionally on model (SP); D replicated
        "residual": [[dp, None], seq_chain, [None]],
        # (B, S, V): vocab on model
        "logits": [[dp, None], [None], ["model", None]],
        # (B, H, S, D) query/out heads
        "act_heads": [[dp, None], ["model", None], [None],
                      ["model", None] if STRATEGY["act_head_dim_fallback"] else [None]],
        # (B, KVH, S, D): kv heads on model, else head_dim
        "act_kv_heads": [[dp, None], ["model", None], [None],
                         ["model", None] if STRATEGY["act_head_dim_fallback"] else [None]],
        # (B, S, F) ffn hidden
        "ffn": [[dp, None], [None], ["model", None]],
        # (E, C, D) expert buffers
        "experts": [["model", None], [None], [dp, None]],
        # (T, D) / (T*k, D) flat token rows (MoE dispatch/combine)
        "tokens": [[dp, None], [None]],
        # (B, S, D) block input: seq GATHERED (Megatron-SP enter-gather) so
        # the TP matmul consumes sharded weights instead of gathering them
        # (iter 6: XLA otherwise gathers the full FFN weight, 6x per layer)
        "block_input": [[dp, None], [None], [None]],
    }


def spec_for_activation(mesh: Mesh, kind: str, shape: Tuple[int, ...]) -> P:
    chains = _act_chains(mesh)[kind]
    dims = []
    used: set = set()

    def flat(c):
        if c is None:
            return ()
        return (c,) if isinstance(c, str) else tuple(c)

    for i, size in enumerate(shape):
        chain = chains[i] if i < len(chains) else [None]
        # drop candidates that reuse an axis already taken by another dim
        filtered = []
        for cand in chain:
            if cand is not None and any(a in used for a in flat(cand)):
                continue
            filtered.append(cand)
        r = _resolve_dim(mesh, size, filtered)
        for a in flat(r):
            used.add(a)
        dims.append(r)
    return P(*dims)


def shard(x, kind: str, all_head_dims: Optional[Tuple[int, ...]] = None):
    """Apply a named activation sharding constraint (no-op without mesh).

    ``all_head_dims`` (q heads, kv heads) drives the iter-7 "auto" policy
    for attention activations: constrain q/k/v only when EVERY head count
    divides the model axis — a mixed state (q constrained, kv propagated,
    measured on qwen110b) is 7x worse than either pure state.
    """
    mesh = get_mesh()
    if mesh is None:
        return x
    mode = STRATEGY["constrain_attn_acts"]
    if kind in ("act_heads", "act_kv_heads"):
        if mode in (False, "0"):
            return x
        if mode == "auto":
            dims = all_head_dims if all_head_dims else (x.shape[1],)
            if any(d % _axis_size(mesh, "model") != 0 for d in dims):
                return x  # let GSPMD propagate (iter 3)
    spec = spec_for_activation(mesh, kind, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding (logical axes from models.layers.PARAM_AXES)
# ---------------------------------------------------------------------------

# logical parameter axis -> fallback chain
def _param_chain(mesh: Mesh, logical: Optional[str], fsdp: bool = True) -> List:
    dp = _dp_axes(mesh)
    return {
        None: [None],
        "layers": [None],
        # training: ZeRO-3/FSDP shard over data(+pod); serving: replicate
        # (per-step all-gather of weights is wrong for latency-bound decode)
        "embed": [dp, None] if fsdp else [None],
        "heads": ["model", None],  # tensor parallel
        "mlp": ["model", None],
        "vocab": ["model", None],
        "experts": ["model", None],  # expert parallel
    }[logical]


def spec_for_param(
    mesh: Mesh, shape: Tuple[int, ...], logical_axes: Tuple[Optional[str], ...],
    scanned: bool = False, fsdp: bool = True,
) -> P:
    dims: List[Any] = []
    used: set = set()
    axes = (("layers",) + tuple(logical_axes)) if scanned else tuple(logical_axes)
    if len(axes) < len(shape):
        axes = axes + (None,) * (len(shape) - len(axes))

    def flat(c):
        if c is None:
            return ()
        return (c,) if isinstance(c, str) else tuple(c)

    for size, logical in zip(shape, axes):
        chain = [
            c
            for c in _param_chain(mesh, logical, fsdp)
            if c is None or not any(a in used for a in flat(c))
        ]
        r = _resolve_dim(mesh, size, chain)
        for a in flat(r):
            used.add(a)
        dims.append(r)
    return P(*dims)


def param_shardings(mesh: Mesh, params: Any, fsdp: bool = True) -> Any:
    """NamedSharding tree matching a parameter pytree.

    Leaf logical axes come from the name registry in models.layers; the
    heuristic here keys on path name (wq/wi/router/embed/...) which the
    init functions registered.
    """
    from ..models.layers import PARAM_AXES

    def leaf_axes(path: str, leaf) -> Tuple[Optional[str], ...]:
        # path like "layers/attn/wq" -> registered under "attn/wq"
        parts = path.split("/")
        for i in range(len(parts)):
            key = "/".join(parts[i:])
            if key in PARAM_AXES:
                return PARAM_AXES[key]
        if parts[-1] in ("embed",):
            return PARAM_AXES.get("embed", ("vocab", "embed"))
        return (None,) * (leaf.ndim if hasattr(leaf, "ndim") else 0)

    def rec(tree, path: str):
        if isinstance(tree, dict):
            return {k: rec(v, f"{path}/{k}" if path else k) for k, v in tree.items()}
        scanned = path.startswith(("layers", "dense_layers", "enc_layers"))
        axes = leaf_axes(path, tree)
        ndim = len(tree.shape)
        want = ndim - (1 if scanned else 0)
        axes = tuple(axes)[:want]
        spec = spec_for_param(mesh, tree.shape, axes, scanned=scanned, fsdp=fsdp)
        return NamedSharding(mesh, spec)

    return rec(params, "")


def opt_state_shardings(mesh: Mesh, params: Any) -> Any:
    """AdamW m/v mirror the (FSDP) parameter sharding; step replicated."""
    ps = param_shardings(mesh, params, fsdp=True)
    return {"m": ps, "v": ps, "step": NamedSharding(mesh, P())}


def batch_shardings(mesh: Mesh, batch: Any) -> Any:
    dp = _dp_axes(mesh)

    def one(x):
        dims = [dp if x.shape[0] % _axis_size(mesh, dp) == 0 else None]
        dims += [None] * (len(x.shape) - 1)
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(one, batch)


def cache_shardings(mesh: Mesh, cache: Any) -> Any:
    """KV/state cache: (L, B, KVH, S, D) — batch on dp if divisible, else
    context-parallel (seq on dp); heads on model, else head_dim on model."""
    dp = _dp_axes(mesh)

    def one(x):
        if x.ndim < 4:
            return NamedSharding(mesh, P())
        L_, B = x.shape[0], x.shape[1]
        dims: List[Any] = [None] * x.ndim
        used: set = set()
        if B % _axis_size(mesh, dp) == 0:
            dims[1] = dp
            used.update(dp)
        h = x.shape[2]
        s = x.shape[3]
        if h % _axis_size(mesh, "model") == 0:
            dims[2] = "model"
            used.add("model")
        elif x.shape[-1] % _axis_size(mesh, "model") == 0:
            dims[-1] = "model"
            used.add("model")
        if dims[1] is None and not any(a in used for a in dp) and s % _axis_size(mesh, dp) == 0:
            dims[3] = dp  # context parallelism for batch=1 long decode
        return NamedSharding(mesh, P(*dims))

    def rec(tree):
        if isinstance(tree, dict):
            return {k: rec(v) for k, v in tree.items()}
        if hasattr(tree, "ndim") and tree.ndim >= 4:
            return one(tree)
        return NamedSharding(mesh, P())

    return rec(cache)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Per-shard workload shapes (mesh-aware task extraction + dispatch)
# ---------------------------------------------------------------------------


@dataclass
class ShardedWorkload:
    """How one tuned-workload call partitions over a mesh.

    ``kwargs`` are the *per-shard* workload shape kwargs (what to tune and
    what key to look up); ``dim_axes`` maps workload dim names (``m``,
    ``n``, ``b``, ``h``/``kvh``) to the mesh axis (or dp-axis tuple) that
    splits them — the dispatch layer turns this into ``shard_map``
    PartitionSpecs.
    """

    kwargs: Dict[str, Any]
    dim_axes: Dict[str, Any]


def shard_workload(
    op: str, kwargs: Dict[str, Any], mesh: Optional[Mesh]
) -> Optional[ShardedWorkload]:
    """Per-shard shape of one tuned workload under a mesh.

    The single source of the fleet's data-parallel/tensor-parallel rules
    for *tuned kernels*: :mod:`repro.integration.extract` uses it to
    decide which shapes to tune when a mesh is active, and
    :class:`repro.integration.dispatch.DispatchContext` uses the same
    rule to pick the per-shard db key it serves inside ``shard_map`` —
    extraction and dispatch can never disagree on the key.

    Dims shard only when the mesh axis size divides them exactly
    (matching the fallback-chain philosophy above); contraction dims
    (``k``, ``s`` of attention scores) never shard — every shard computes
    an exact local result and no cross-shard reduction is needed.
    Returns ``None`` when the op is not mesh-servable or nothing divides.
    """
    if mesh is None:
        return None
    dp = _dp_axes(mesh)
    dpn = _axis_size(mesh, dp)
    mdl = mesh.shape["model"] if "model" in mesh.axis_names else 1
    kw = dict(kwargs)
    axes: Dict[str, Any] = {}
    if op == "dense":
        # rows over data-parallel, columns over tensor-parallel; the
        # contraction dim k stays whole
        if dpn > 1 and kw.get("m", 0) % dpn == 0 and kw.get("m", 0) >= dpn:
            kw["m"] //= dpn
            axes["m"] = dp
        if mdl > 1 and kw.get("n", 0) % mdl == 0 and kw.get("n", 0) >= mdl:
            kw["n"] //= mdl
            axes["n"] = "model"
    elif op == "batch_matmul":
        # the leading batch dim carries heads (attention contractions) or
        # experts (MoE): model axis first, data-parallel as fallback
        if mdl > 1 and kw.get("b", 0) % mdl == 0 and kw.get("b", 0) >= mdl:
            kw["b"] //= mdl
            axes["b"] = "model"
        elif dpn > 1 and kw.get("b", 0) % dpn == 0 and kw.get("b", 0) >= dpn:
            kw["b"] //= dpn
            axes["b"] = dp
    elif op in ("attention", "attention_decode"):
        # heads over model (q and kv head counts must both divide so GQA
        # groups stay intact per shard), batch over data-parallel
        h, kvh = kw.get("h", 0), kw.get("kvh", 0)
        if mdl > 1 and h and kvh and h % mdl == 0 and kvh % mdl == 0:
            kw["h"] //= mdl
            kw["kvh"] //= mdl
            axes["h"] = "model"
        if dpn > 1 and kw.get("b", 0) % dpn == 0 and kw.get("b", 0) >= dpn:
            kw["b"] //= dpn
            axes["b"] = dp
    else:
        return None
    if not axes:
        return None
    return ShardedWorkload(kwargs=kw, dim_axes=axes)
