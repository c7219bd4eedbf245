"""Automatic task extraction from whole models (Ansor-style, end-to-end).

Instead of hand-coding per-model hot shapes, we trace the model's forward
pass with ``jax.make_jaxpr`` (abstract — no allocation, works at full
model scale) and walk the jaxpr recursively, mapping primitive sites to
registered tensor-program workloads in :mod:`repro.core.workloads`:

* ``dot_general``  -> ``dense`` (no batch dims) or ``batch_matmul``
  (leading spatial dims of the lhs/rhs fold into m/n; contraction dims
  fold into k);
* ``rsqrt``        -> ``rmsnorm`` over (tokens, d_model) — the model's
  norms lower to exactly one ``rsqrt`` each;
* ``exp``          -> ``sfm`` (row softmax) over the flattened operand —
  the attention-softmax sites;
* anything else    -> skipped.

``scan`` bodies multiply site occurrence counts by the trip count, so a
30-layer stacked-scan transformer yields weight-30 tasks rather than 30
copies.  Tasks dedup by the *structural hash* of the instantiated
workload PrimFunc (:func:`repro.search.measure.hashing.primfunc_structural_hash`),
summing occurrence weights — the scheduler then allocates trials by those
weights, and :class:`repro.integration.dispatch.DispatchContext` swaps the
tuned traces back into the model by the same workload keys.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax

from ..configs.base import ModelConfig, ShapeConfig
from ..core.workloads import get_workload
from ..obs import emit, trace_enabled
from ..search.database import workload_key
from ..search.measure.hashing import primfunc_structural_hash
from ..search.task_scheduler import TuneTask

TOKEN_TILE = 128  # default representative token block (batch=1 x seq=128)

# ops the extractor understands; everything else is skipped
EXTRACTABLE_OPS = ("dense", "batch_matmul", "rmsnorm", "sfm", "attention")

# ops extracted from the decode trace (serving): dense/bmm keyed on
# m = batch, plus the single-token cache-attention workload.  sfm is
# omitted — decode softmax rows ride inside attention_decode.
DECODE_EXTRACTABLE_OPS = (
    "dense", "batch_matmul", "rmsnorm", "attention_decode",
)


def _skip(site: str, reason: str) -> None:
    """Dropped-site telemetry: every site the extractor cannot express is
    dispatch coverage lost, so it must be visible (an ``extract.skip``
    trace event) instead of silent."""
    if trace_enabled():
        emit("extract.skip", site=site, reason=reason)


@dataclass
class TaskSite:
    """One primitive site mapped to a workload, pre-dedup.

    ``dispatchable`` marks sites whose memory layout the dispatch layer
    can serve today (``x @ w`` with w stored (k, n); canonical-layout
    ``batch_matmul`` — the attention score/value contractions and MoE
    expert FFNs; rmsnorm).  A transposed-weight matmul (e.g. the
    tied-embedding unembed) is still a legitimate *tuning* target but
    cannot be swapped back into the model yet, so benchmarks that spend
    trials only where they can cash them set ``dispatchable_only=True``.
    """

    op: str
    kwargs: Dict[str, Any]
    count: float  # occurrence count (scan trip counts folded in)
    dispatchable: bool = False


@dataclass
class ExtractedTask:
    """A deduplicated, weighted tuning task."""

    key: str
    op: str
    kwargs: Dict[str, Any]
    weight: float
    struct_hash: str
    flops: int
    dispatchable: bool = False

    def to_tune_task(self, use_mxu: bool = True) -> TuneTask:
        func = get_workload(self.op, **self.kwargs)
        mxu = use_mxu and self.op in (
            "dense", "batch_matmul", "attention", "attention_decode",
        )
        return TuneTask(key=self.key, func=func, weight=self.weight, use_mxu=mxu)


# ---------------------------------------------------------------------------
# Attention-site recording (trace-time hook)
# ---------------------------------------------------------------------------

_REC_TLS = threading.local()


def current_attention_recorder() -> Optional["AttentionSiteRecorder"]:
    """The active recorder, read by ``models.layers.chunked_attention``."""
    stack = getattr(_REC_TLS, "stack", None)
    return stack[-1] if stack else None


@dataclass
class AttentionSiteRecorder:
    """Collects fused-attention call sites while the model traces.

    Attention is one whole-subgraph workload, not a single jaxpr
    primitive — the chunked online-softmax lowering scatters it over a
    scan of contractions — so instead of pattern-matching the jaxpr, the
    attention hook in the model layers reports its static call
    configuration here during the same ``jax.make_jaxpr`` trace the
    primitive walk uses.  One record per *traced* call; scan multiplicity
    is restored from the config's static window pattern (see
    :func:`attention_sites`).
    """

    sites: List[Dict[str, Any]] = field(default_factory=list)

    def add(
        self, *, q_shape, kvh, kv_seq, causal, window, softcap, scale,
        q_offset, kind: str = "prefill",
    ) -> None:
        traced = jax.core.Tracer
        self.sites.append(
            dict(
                q_shape=tuple(int(x) for x in q_shape),
                kvh=int(kvh),
                kv_seq=int(kv_seq),
                causal=bool(causal),
                window=(
                    "traced" if isinstance(window, traced)
                    else (int(window) if window is not None else 0)
                ),
                softcap=(
                    "traced" if isinstance(softcap, traced)
                    else (float(softcap) if softcap else 0.0)
                ),
                scale=(None if scale is None else float(scale)),
                q_offset=(
                    "traced" if isinstance(q_offset, traced) else int(q_offset)
                ),
                kind=kind,  # "prefill" (chunked_attention) | "decode"
            )
        )

    def __enter__(self) -> "AttentionSiteRecorder":
        stack = getattr(_REC_TLS, "stack", None)
        if stack is None:
            stack = _REC_TLS.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _REC_TLS.stack.pop()


def attention_sites(
    cfg: ModelConfig, recorded: List[Dict[str, Any]]
) -> List[TaskSite]:
    """Weighted attention TaskSites from trace-time records.

    Each record is one traced call; a periodic-window layer scan traces
    its body once, so the true occurrence count of a causal record with
    static window ``w`` is the number of layers carrying that window
    (split across the records that share it).  Records the workload
    cannot express — traced window/softcap (aperiodic patterns), decode
    offsets, non-square kv, non-default scale — are skipped: those sites
    keep the chunked path, whose contractions are extracted as
    ``batch_matmul`` tasks anyway.
    """
    from ..models.transformer import layer_windows

    recorded = [r for r in recorded if r.get("kind", "prefill") == "prefill"]
    windows = layer_windows(cfg)
    rec_by_window: Dict[int, int] = {}
    for r in recorded:
        if r["causal"] and isinstance(r["window"], int):
            w = r["window"]
            if w >= r["q_shape"][2]:
                w = 0  # window >= seq is global (canonical form)
            rec_by_window[w] = rec_by_window.get(w, 0) + 1
    sites: List[TaskSite] = []
    for r in recorded:
        if r["window"] == "traced":
            _skip("attention", "traced_window")
            continue
        if r["softcap"] == "traced":
            _skip("attention", "traced_softcap")
            continue
        if r["q_offset"] == "traced":
            _skip("attention", "traced_offset")
            continue
        if r["q_offset"] != 0:
            _skip("attention", "decode_offset")
            continue
        B, H, S, D = r["q_shape"]
        KVH = r["kvh"]
        if r["kv_seq"] != S:
            _skip("attention", "cross_attention")
            continue  # cross-attention (S != T): chunked path
        if H % KVH != 0:
            _skip("attention", "ragged_gqa")
            continue
        if r["scale"] is not None and abs(r["scale"] - D**-0.5) > 1e-12:
            _skip("attention", "nondefault_scale")
            continue
        w = r["window"]
        if w and not r["causal"]:
            _skip("attention", "noncausal_window")
            continue  # the workload's window mask implies causality
        if w >= S:
            w = 0  # a window covering the whole sequence IS global
        if r["causal"]:
            total = sum(
                1
                for lw in windows
                if (int(lw) if int(lw) < S else 0) == w
            )
            n_rec = rec_by_window.get(w, 1)
            weight = total / n_rec if total else 1.0
        else:
            # encoder self-attention: one record per enc-scan body trace
            weight = float(cfg.enc_layers or 1)
        sites.append(
            TaskSite(
                "attention",
                dict(
                    b=B, h=H, kvh=KVH, s=S, d=D,
                    causal=int(r["causal"]), window=int(w),
                    softcap=float(r["softcap"]),
                ),
                weight,
                dispatchable=True,
            )
        )
    return sites


def decode_attention_sites(
    cfg: ModelConfig, recorded: List[Dict[str, Any]]
) -> List[TaskSite]:
    """Weighted ``attention_decode`` TaskSites from decode-trace records.

    Every single-token cache-attention call (self-attention at its ring
    slot, cross-attention against a static encoder cache) maps to the same
    workload: the key holds only the static shape (b, h, kvh, t, d,
    softcap) — the window and the traced per-slot lengths ride in as BIAS
    data at dispatch time, so layers differing only in window share one
    tuned kernel.  Scan multiplicity is restored per distinct shape: a
    periodic layer scan traces its body once per period-group, so each
    record sharing a shape carries ``n_layers / n_records`` layers.
    """
    recs = [r for r in recorded if r.get("kind") == "decode"]
    kept: List[Dict[str, Any]] = []
    for r in recs:
        B, H, S, D = r["q_shape"]
        if r["window"] == "traced":
            _skip("attention_decode", "traced_window")
            continue
        if r["softcap"] == "traced":
            _skip("attention_decode", "traced_softcap")
            continue
        if S != 1:
            _skip("attention_decode", "not_single_token")
            continue
        if H % r["kvh"] != 0:
            _skip("attention_decode", "ragged_gqa")
            continue
        if r["scale"] is not None and abs(r["scale"] - D**-0.5) > 1e-12:
            _skip("attention_decode", "nondefault_scale")
            continue
        kept.append(
            dict(
                b=B, h=H, kvh=r["kvh"], t=r["kv_seq"], d=D,
                softcap=float(r["softcap"]),
            )
        )
    by_shape: Dict[Tuple, int] = {}
    for kw in kept:
        sig = tuple(sorted(kw.items()))
        by_shape[sig] = by_shape.get(sig, 0) + 1
    Ln = max(int(cfg.n_layers), 1)
    return [
        TaskSite(
            "attention_decode", kw,
            Ln / by_shape[tuple(sorted(kw.items()))],
            dispatchable=True,
        )
        for kw in kept
    ]


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn) -> List[Tuple[Any, int]]:
    """(inner jaxpr, trip-count multiplier) pairs nested in an eqn."""
    mult = 1
    if eqn.primitive.name == "scan":
        mult = int(eqn.params.get("length", 1))
    out: List[Tuple[Any, int]] = []

    def add(v):
        if hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
            out.append((v.jaxpr, mult))  # ClosedJaxpr
        elif hasattr(v, "eqns"):
            out.append((v, mult))  # open Jaxpr

    for v in eqn.params.values():
        add(v)
        if isinstance(v, (tuple, list)):
            for u in v:
                add(u)
    return out


def _walk_eqns(jaxpr, mult: int, visit: Callable[[Any, int], None]) -> None:
    for eqn in jaxpr.eqns:
        visit(eqn, mult)
        for sub, m2 in _sub_jaxprs(eqn):
            _walk_eqns(sub, mult * m2, visit)


def _prod(xs: Iterable[int]) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _dot_site(eqn) -> Optional[TaskSite]:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    b = _prod(lhs[i] for i in lb)
    k = _prod(lhs[i] for i in lc)
    m = _prod(lhs[i] for i in range(len(lhs)) if i not in set(lb) | set(lc))
    n = _prod(rhs[i] for i in range(len(rhs)) if i not in set(rb) | set(rc))
    if min(m, n, k) < 1:
        return None
    if b > 1:
        # the batch_matmul dispatch hook serves a(..., m, k) @ b(..., k, n)
        # with matching leading batch dims: batch dims lead both operands
        # in order, lhs contracts its last dim, rhs its second-to-last —
        # the layout the attention score/value contractions (via bmm_op)
        # and the MoE expert FFN einsums trace to.  Anything else (e.g.
        # tbg-style head-interleaved layouts) tunes but can't swap in.
        r = len(lhs)
        disp = (
            len(rhs) == r
            and tuple(lb) == tuple(range(r - 2))
            and tuple(rb) == tuple(range(r - 2))
            and tuple(lc) == (r - 1,)
            and tuple(rc) == (r - 2,)
        )
        return TaskSite(
            "batch_matmul", dict(b=b, m=m, n=n, k=k), 1.0, dispatchable=disp
        )
    # the dense dispatch hook serves x(..., k) @ w(k, n), and — via
    # transpose-at-load — x(..., k) @ wT(n, k): the tied-embedding unembed
    # ``bsd,vd->bsv``.  Either way the lhs contracts its trailing dims and
    # the 2-D rhs contracts exactly one dim.
    disp = (
        len(rhs) == 2
        and tuple(rc) in ((0,), (1,))
        and tuple(lc) == tuple(range(len(lhs) - len(lc), len(lhs)))
    )
    return TaskSite("dense", dict(m=m, n=n, k=k), 1.0, dispatchable=disp)


def _rsqrt_site(eqn, d_model: int, eps: float) -> Optional[TaskSite]:
    if d_model <= 0:
        return None
    shape = eqn.invars[0].aval.shape
    tokens = max(_prod(shape), 1)
    # eps is part of the workload (baked into the PrimFunc expression) and
    # of the key — it must match what the model passes at dispatch time
    return TaskSite(
        "rmsnorm", dict(tokens=tokens, d=d_model, eps=eps), 1.0, dispatchable=True
    )


def _exp_site(eqn) -> Optional[TaskSite]:
    shape = eqn.invars[0].aval.shape
    if len(shape) < 2 or shape[-1] < 2:
        return None  # scalar / correction-factor exp, not a softmax row
    return TaskSite("sfm", dict(m=_prod(shape[:-1]), n=int(shape[-1])), 1.0)


def sites_from_jaxpr(
    closed_jaxpr, d_model: int = 0, norm_eps: float = 1e-6
) -> List[TaskSite]:
    """All extractable primitive sites of a (closed) jaxpr, pre-dedup."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    sites: List[TaskSite] = []

    def visit(eqn, mult):
        name = eqn.primitive.name
        site = None
        if name == "dot_general":
            site = _dot_site(eqn)
        elif name == "rsqrt":
            site = _rsqrt_site(eqn, d_model, norm_eps)
        elif name == "exp":
            site = _exp_site(eqn)
        if site is not None:
            site.count = float(mult)
            sites.append(site)

    _walk_eqns(jaxpr, 1, visit)
    return sites


# ---------------------------------------------------------------------------
# Dedup + weighting
# ---------------------------------------------------------------------------


def _task_flops(op: str, kw: Dict[str, Any]) -> int:
    if op == "dense":
        return 2 * kw["m"] * kw["n"] * kw["k"]
    if op == "batch_matmul":
        return 2 * kw["b"] * kw["m"] * kw["n"] * kw["k"]
    if op == "rmsnorm":
        return 4 * kw["tokens"] * kw["d"]
    if op == "sfm":
        return 8 * kw["m"] * kw["n"]
    if op == "attention":
        # scores + value contractions (softmax flops are second-order)
        return 4 * kw["b"] * kw["h"] * kw["s"] * kw["s"] * kw["d"]
    if op == "attention_decode":
        # one query token against a length-t cache
        return 4 * kw["b"] * kw["h"] * kw["t"] * kw["d"]
    return 0


def shard_sites(sites: Iterable[TaskSite], mesh) -> List[TaskSite]:
    """Rewrite site shapes to the per-shard shapes a mesh would run.

    Under ``shard_map`` each device executes the *local* block of every
    primitive, so the shapes worth tuning (and the keys dispatch will look
    up at serving time) are the per-shard ones.  Delegates the partitioning
    rules to :func:`repro.distributed.sharding.shard_workload` — the same
    function the dispatch layer uses — so extracted keys and served keys
    can never drift apart.  Sites the mesh cannot split (or that
    ``shard_workload`` declines) pass through unchanged, with an
    ``extract.shard`` event recording each rewrite.
    """
    from ..distributed.sharding import shard_workload

    if mesh is None:
        return list(sites)
    out: List[TaskSite] = []
    for s in sites:
        sw = shard_workload(s.op, s.kwargs, mesh)
        if sw is None or sw.kwargs == s.kwargs:
            out.append(s)
            continue
        if trace_enabled():
            emit(
                "extract.shard",
                op=s.op,
                global_kwargs=dict(s.kwargs),
                shard_kwargs=dict(sw.kwargs),
                axes={k: list(v) if isinstance(v, tuple) else v
                      for k, v in sw.dim_axes.items()},
            )
        out.append(
            TaskSite(
                op=s.op,
                kwargs=dict(sw.kwargs),
                count=s.count,
                dispatchable=s.dispatchable,
            )
        )
    return out


def dedup_sites(
    sites: Iterable[TaskSite], min_task_elems: int = 4096
) -> List[ExtractedTask]:
    """Collapse repeated shapes into weighted tasks (structural-hash dedup).

    ``min_task_elems`` drops degenerate sites (e.g. the online-softmax
    correction factor ``exp`` over an n=1 column) whose tuning could never
    pay for itself.  A merged task's ``weight`` counts *all* structurally
    identical sites and ``dispatchable`` is true if *any* of them can be
    served — callers that must weight only servable occurrences (the
    benchmark) filter sites before dedup via ``dispatchable_only``.
    """
    by_hash: Dict[str, ExtractedTask] = {}
    for s in sites:
        elems = _task_flops(s.op, s.kwargs) // 2
        if elems < min_task_elems:
            continue
        func = get_workload(s.op, **s.kwargs)
        h = primfunc_structural_hash(func)
        if h in by_hash:
            by_hash[h].weight += s.count
            by_hash[h].dispatchable = by_hash[h].dispatchable or s.dispatchable
        else:
            by_hash[h] = ExtractedTask(
                key=workload_key(s.op, **s.kwargs),
                op=s.op,
                kwargs=dict(s.kwargs),
                weight=s.count,
                struct_hash=h,
                flops=_task_flops(s.op, s.kwargs),
                dispatchable=s.dispatchable,
            )
    out = list(by_hash.values())
    out.sort(key=lambda t: (-t.weight * t.flops, t.key))
    return out


# ---------------------------------------------------------------------------
# Model-level entry point
# ---------------------------------------------------------------------------


def model_forward_jaxpr(cfg: ModelConfig, batch: int = 1, seq: int = TOKEN_TILE):
    """Abstractly trace ``models.transformer.forward`` for one config."""
    from ..models import transformer as T
    from ..models.registry import prefill_input_specs

    params = T.param_specs(cfg)
    shape = ShapeConfig("extract", seq, batch, "prefill")
    inputs = prefill_input_specs(cfg, shape)
    return jax.make_jaxpr(lambda p, ins: T.forward(cfg, p, **ins))(params, inputs)


def _resolve_mesh(mesh):
    """``"auto"`` means the thread's active mesh (``use_mesh`` block);
    ``None`` explicitly disables per-shard shaping."""
    if isinstance(mesh, str) and mesh == "auto":
        from ..distributed.sharding import get_mesh

        return get_mesh()
    return mesh


def extract_tasks(
    cfg: ModelConfig,
    batch: int = 1,
    seq: int = TOKEN_TILE,
    use_mxu: bool = True,
    min_task_elems: int = 4096,
    max_tasks: int = 0,
    ops: Tuple[str, ...] = EXTRACTABLE_OPS,
    dispatchable_only: bool = False,
    mesh="auto",
) -> List[TuneTask]:
    """Extract weighted tuning tasks from a model config's forward pass.

    Generic across every config in ``repro.configs`` — no per-model shape
    tables.  ``max_tasks > 0`` keeps only the top tasks by
    weight x flops (the end-to-end-dominant ones); ``dispatchable_only``
    further restricts to sites the dispatch layer can swap back into the
    model — together these are what the CPU benchmark uses to spend its
    trial budget only where it can cash it.  When a mesh is active (or
    passed explicitly) sites are rewritten to per-shard shapes first, so
    tuning spends trials on the block sizes each device will actually run.
    """
    extracted = extract_task_specs(
        cfg, batch=batch, seq=seq, min_task_elems=min_task_elems,
        max_tasks=max_tasks, ops=ops, dispatchable_only=dispatchable_only,
        mesh=mesh,
    )
    return [t.to_tune_task(use_mxu=use_mxu) for t in extracted]


def extract_task_specs(
    cfg: ModelConfig,
    batch: int = 1,
    seq: int = TOKEN_TILE,
    min_task_elems: int = 4096,
    max_tasks: int = 0,
    ops: Tuple[str, ...] = EXTRACTABLE_OPS,
    dispatchable_only: bool = False,
    mesh="auto",
) -> List[ExtractedTask]:
    """Like :func:`extract_tasks` but returns the rich task records."""
    recorder = AttentionSiteRecorder()
    with recorder:
        jaxpr = model_forward_jaxpr(cfg, batch=batch, seq=seq)
    sites = sites_from_jaxpr(jaxpr, d_model=cfg.d_model, norm_eps=cfg.norm_eps)
    sites += attention_sites(cfg, recorder.sites)
    sites = [s for s in sites if s.op in ops]
    if dispatchable_only:
        sites = [s for s in sites if s.dispatchable]
    sites = shard_sites(sites, _resolve_mesh(mesh))
    tasks = dedup_sites(sites, min_task_elems=min_task_elems)
    return _apply_max_tasks(cfg, tasks, max_tasks, ops, "attention")


def _apply_max_tasks(
    cfg: ModelConfig,
    tasks: List[ExtractedTask],
    max_tasks: int,
    ops: Tuple[str, ...],
    attn_op: str,
) -> List[ExtractedTask]:
    if max_tasks <= 0 or len(tasks) <= max_tasks:
        return tasks
    dropped = tasks[max_tasks:]
    tasks = tasks[:max_tasks]
    # the weight x flops ranking undervalues attention (its cost is
    # softmax + memory traffic, not just matmul flops), and it is the
    # one op class whose blocks only tune through its own task — keep
    # the heaviest attention task alive under the cap
    if (
        attn_op in ops
        and any(d.op == attn_op for d in dropped)
        and not any(t.op == attn_op for t in tasks)
    ):
        kept_attn = next(d for d in dropped if d.op == attn_op)
        dropped = [d for d in dropped if d is not kept_attn]
        tasks[-1], dropped = kept_attn, dropped + [tasks[-1]]
    # no silent caps: record what fell off the end
    import logging

    logging.getLogger(__name__).info(
        "extract_tasks(%s): kept %d tasks, dropped %d (%s)",
        cfg.name, len(tasks), len(dropped),
        ", ".join(d.key for d in dropped),
    )
    return tasks


# ---------------------------------------------------------------------------
# Decode (serving) entry point
# ---------------------------------------------------------------------------


def model_decode_jaxpr(
    cfg: ModelConfig, batch: int = 4, max_seq: int = TOKEN_TILE
):
    """Abstractly trace one ``decode_step`` in the continuous-batching
    arena layout: a per-slot ``(batch,)`` position vector, one token per
    slot, the fixed-shape KV cache of ``max_seq``.  This is the program
    the serving scheduler actually runs every tick — dense/bmm sites key
    on ``m = batch`` and attention reaches the recorder as single-token
    cache attention."""
    import jax.numpy as jnp

    from ..models import transformer as T

    params = T.param_specs(cfg)
    cache = dict(jax.eval_shape(lambda: T.init_cache(cfg, batch, max_seq)))
    cache["pos"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    toks = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    return jax.make_jaxpr(lambda p, c, t: T.decode_step(cfg, p, c, t))(
        params, cache, toks
    )


def model_serve_jaxpr(
    cfg: ModelConfig,
    batch: int = 4,
    max_seq: int = TOKEN_TILE,
    chunk: int = 1,
    paged: bool = False,
    page_size: int = 16,
    total_pages: int = 0,
):
    """Abstractly trace one ``serve_step`` tick (paged serving tier).

    The ``chunk``-wide program the scheduler runs when in-tick prefill is
    on (``chunk == prefill_chunk``; ``chunk == 1`` is the decode-only
    tick), optionally through the paged cache layout — ``(L, n_pages,
    KVH, page_size, D)`` pools plus a ``(batch, P)`` page table.  The
    attention workload is unchanged by paging (the page view restores
    ``t = kv_len``), but dense/bmm/rmsnorm sites key on ``m = batch *
    chunk``, which is what the mixed tick actually runs."""
    import jax.numpy as jnp

    from ..models import transformer as T
    from ..serving.kv import snap_page_size

    params = T.param_specs(cfg)
    cache = dict(jax.eval_shape(lambda: T.init_cache(cfg, batch, max_seq)))
    cache["pos"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    if paged:
        Ln, _, kvh, kv_len, hd = cache["k"].shape
        ps = snap_page_size(kv_len, page_size)
        pages_per_slot = kv_len // ps
        n_pages = int(total_pages) or batch * pages_per_slot
        pool = jax.ShapeDtypeStruct(
            (Ln, n_pages, kvh, ps, hd), cache["k"].dtype
        )
        cache["k"] = cache["v"] = pool
        cache["page_table"] = jax.ShapeDtypeStruct(
            (batch, pages_per_slot), jnp.int32
        )
    toks = jax.ShapeDtypeStruct((batch, max(1, chunk)), jnp.int32)
    valid = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, t, va: T.serve_step(cfg, p, c, t, va)
    )(params, cache, toks, valid)


def extract_decode_task_specs(
    cfg: ModelConfig,
    batch: int = 4,
    max_seq: int = TOKEN_TILE,
    min_task_elems: int = 1024,
    max_tasks: int = 0,
    ops: Tuple[str, ...] = DECODE_EXTRACTABLE_OPS,
    dispatchable_only: bool = False,
    mesh="auto",
    chunk: int = 0,
    paged: bool = False,
    page_size: int = 16,
) -> List[ExtractedTask]:
    """Decode-shape tuning tasks for a serving configuration.

    The decode counterpart of :func:`extract_task_specs`: same walk, same
    dedup, but over :func:`model_decode_jaxpr` — so the extracted keys are
    exactly what :class:`~repro.integration.dispatch.DispatchContext`
    looks up at serving-decode trace time.  ``min_task_elems`` defaults
    lower than prefill because decode shapes are small by construction
    (m = batch, not batch x seq) yet run every generated token.

    ``chunk > 0`` / ``paged`` additionally walk the ``serve_step``
    program of the paged serving tier (:func:`model_serve_jaxpr`) with
    that chunk width, merging its sites — the mixed prefill+decode tick
    runs dense/bmm at ``m = batch * chunk``, and tuning those keys keeps
    in-tick prefill on tuned kernels too.  Unsupported model families
    (SSD / encoder decoders) silently skip the serve walk.
    """
    recorder = AttentionSiteRecorder()
    with recorder:
        jaxpr = model_decode_jaxpr(cfg, batch=batch, max_seq=max_seq)
    sites = sites_from_jaxpr(jaxpr, d_model=cfg.d_model, norm_eps=cfg.norm_eps)
    sites += decode_attention_sites(cfg, recorder.sites)
    if (chunk > 0 or paged) and not (
        cfg.attn_free or cfg.ssm_state or cfg.enc_layers
    ):
        with AttentionSiteRecorder():  # chunk attention has no tuned shape
            sjaxpr = model_serve_jaxpr(
                cfg, batch=batch, max_seq=max_seq, chunk=max(1, chunk),
                paged=paged, page_size=page_size,
            )
        sites += sites_from_jaxpr(
            sjaxpr, d_model=cfg.d_model, norm_eps=cfg.norm_eps
        )
    sites = [s for s in sites if s.op in ops]
    if dispatchable_only:
        sites = [s for s in sites if s.dispatchable]
    sites = shard_sites(sites, _resolve_mesh(mesh))
    tasks = dedup_sites(sites, min_task_elems=min_task_elems)
    return _apply_max_tasks(cfg, tasks, max_tasks, ops, "attention_decode")


def extract_decode_tasks(
    cfg: ModelConfig,
    batch: int = 4,
    max_seq: int = TOKEN_TILE,
    use_mxu: bool = True,
    min_task_elems: int = 1024,
    max_tasks: int = 0,
    ops: Tuple[str, ...] = DECODE_EXTRACTABLE_OPS,
    dispatchable_only: bool = False,
    mesh="auto",
    chunk: int = 0,
    paged: bool = False,
    page_size: int = 16,
) -> List[TuneTask]:
    """Like :func:`extract_decode_task_specs` but returns ``TuneTask``s."""
    extracted = extract_decode_task_specs(
        cfg, batch=batch, max_seq=max_seq, min_task_elems=min_task_elems,
        max_tasks=max_tasks, ops=ops, dispatchable_only=dispatchable_only,
        mesh=mesh, chunk=chunk, paged=paged, page_size=page_size,
    )
    return [t.to_tune_task(use_mxu=use_mxu) for t in extracted]
