"""Tuned-kernel dispatch: swap database-backed traces into model forward.

``DispatchContext`` is the consumer side of the end-to-end loop: given a
tuning :class:`~repro.search.database.Database`, it looks up the best
record per workload key, replays the stored trace through the validator,
lowers the schedule with the jnp backend, jits it once, and serves the
compiled callable to the model layers — which call in through the hooks
in :mod:`repro.models.layers` (``dense_op`` / ``rmsnorm``) while the
context is active::

    db = Database("results/tuning_db.json")
    with DispatchContext(db, tasks=extract_tasks(cfg)) as ctx:
        logits = jax.jit(lambda p, t: forward(cfg, p, tokens=t))(params, toks)
    print(ctx.stats)   # {"hits": ..., "misses": ...}

Fallback is transparent: no database record, an invalid stored trace, or
a shape the context has never seen all return ``None`` from the lookup
and the layer keeps its jnp reference path.  A record that exists but
cannot be lowered, or a mesh-served kernel that fails to build, raises:
running the reference instead would hide a broken kernel behind a
passing run.  Lookups happen at *trace
time* (shapes are static under jit), so a dispatched forward bakes the
tuned kernels into its jaxpr and pays zero per-call dispatch cost.

Gradients: tuned kernels are forward-optimized, so each swapped call is
wrapped in ``jax.custom_vjp`` whose backward is the VJP of the jnp
reference op — training under a context differentiates correctly without
requiring the lowered loop nest to be reverse-differentiable.

``mode="default"`` compiles the *first valid space sample* per workload
instead of the database best: the canonical untuned schedule, used as the
measured untuned baseline in ``benchmarks/end_to_end.py``.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..backends.registry import get_backend, resolve_backend_spec
from ..core.modules import SpaceGenerator, default_modules
from ..core.tir import PrimFunc
from ..core.validator import first_valid_schedule, validate_trace
from ..distributed.sharding import get_mesh, shard_workload
from ..obs import emit, trace_enabled
from ..search.database import Database, parse_workload_key, workload_key

# active-context stack; layers read the top via current().  Thread-local so
# parallel serving threads with different contexts don't cross-dispatch.
_TLS = threading.local()


def current() -> Optional["DispatchContext"]:
    """The innermost active DispatchContext, or None."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


@dataclass
class CompiledKernel:
    """A lowered, jitted workload ready to swap into the model."""

    key: str
    func: PrimFunc
    fn: Callable  # callable(dict inputs) -> dict outputs (jitted)
    out_name: str
    source: str  # "database" | "default"
    latency_s: float = float("inf")
    grad_fn: Optional[Callable] = None  # custom_vjp-wrapped positional call
    meta: Optional[Dict[str, Any]] = None  # lowering provenance (backend,
                                           # snapped Pallas blocks, ...)
    # (mesh, fn): the shard_map-wrapped grad_fn serving this per-shard
    # kernel on *global* operands under that mesh; separate from grad_fn
    # because the two expect different operand sizes
    mesh_grad_fn: Optional[tuple] = None


class DispatchContext:
    """Looks up best traces by workload key and serves compiled kernels.

    Parameters
    ----------
    database:
        A ``Database`` instance or a path to one.  Optional in
        ``mode="default"``.
    tasks:
        Optional iterable of ``TuneTask`` (or anything with ``.key`` and
        ``.func``) naming the workloads this context may dispatch.  When
        omitted, every parseable key in the database becomes dispatchable.
    mode:
        ``"best"`` (default): compile the best database record per key;
        keys without a record miss and fall back.  ``"default"``: compile
        the first valid space sample per key — the untuned baseline.
    """

    def __init__(
        self,
        database: Optional[Any] = None,
        tasks: Optional[Sequence[Any]] = None,
        mode: str = "best",
        use_mxu: bool = True,
        default_seed_scan: int = 8,
        backend: Optional[str] = None,
    ):
        if mode not in ("best", "default"):
            raise ValueError(f"unknown dispatch mode {mode!r}")
        self.db: Optional[Database] = (
            Database(database) if isinstance(database, str) else database
        )
        self.mode = mode
        self.use_mxu = use_mxu
        self.default_seed_scan = default_seed_scan
        # the lowering backend this context serves: the *same* spec the
        # measurement stack built candidates through (jnp-measures /
        # pallas-serves parity would silently break otherwise).  None ->
        # the ambient REPRO_BACKEND default, matching the runners'.
        # Resolve eagerly: a typo'd spec must raise here, not surface as
        # silent universal misses when kernel() swallows lowering errors.
        self.backend = resolve_backend_spec(backend)
        get_backend(self.backend)
        self.stats: Dict[str, int] = {
            "hits": 0,
            "misses": 0,
            "attention_fused": 0,
            "attention_tuned": 0,
            "attention_decode_tuned": 0,
            "mesh_sharded": 0,
        }
        self.hits_by_key: Dict[str, int] = {}
        # per-key outcome table with labeled reasons — the two bare
        # counters above stay for backward compat; stats_by_key() exposes
        # the granular view and dispatch.* trace events mirror it
        self._by_key: Dict[str, Dict[str, Any]] = {}
        self.miss_reasons: Dict[str, str] = {}  # key -> why kernel() is None
        self._funcs: Dict[str, PrimFunc] = {}
        self._task_mxu: Dict[str, bool] = {}
        self._compiled: Dict[str, Optional[CompiledKernel]] = {}
        if tasks is not None:
            for t in tasks:
                self._funcs[t.key] = t.func
                self._task_mxu[t.key] = getattr(t, "use_mxu", False)
        elif self.db is not None:
            from ..core.workloads import WORKLOADS, get_workload

            for key in self.db.keys():
                try:
                    name, kw = parse_workload_key(key)
                    if name in WORKLOADS:
                        self._funcs[key] = get_workload(name, **kw)
                except Exception:
                    continue  # foreign key (e.g. operator-bench workload)

    # -- context management -------------------------------------------------

    def __enter__(self) -> "DispatchContext":
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _TLS.stack.pop()

    # -- compilation --------------------------------------------------------

    def keys(self) -> List[str]:
        return list(self._funcs.keys())

    def tuned_keys(self) -> List[str]:
        """Keys for which the database holds at least one record."""
        if self.db is None:
            return []
        return [k for k in self._funcs if self.db.best(k) is not None]

    def _schedule_for(self, key: str, func: PrimFunc):
        """(schedule, source, latency); schedule None -> source is the
        miss reason ("no_database" | "no_record" | "invalid_trace" |
        "no_valid_schedule")."""
        if self.mode == "best":
            if self.db is None:
                return None, "no_database", float("inf")
            rec = self.db.best(key)
            if rec is None:
                return None, "no_record", float("inf")
            v = validate_trace(func, rec.trace())
            if not v.ok:
                return None, "invalid_trace", float("inf")
            return v.schedule, "database", rec.latency_s
        # mode == "default": the canonical untuned schedule.  Use the
        # task's own space configuration when known so this is the exact
        # program the scheduler's warm-start seeded the search with.
        if key in self._task_mxu:
            mxu = self._task_mxu[key]
        else:
            name, _ = parse_workload_key(key)
            mxu = self.use_mxu and name in (
                "dense", "batch_matmul", "gmm", "attention",
                "attention_decode",
            )
        space = SpaceGenerator(default_modules(use_mxu=mxu))
        sch = first_valid_schedule(func, space, self.default_seed_scan)
        if sch is None:
            return None, "no_valid_schedule", float("inf")
        return sch, "default", float("inf")

    def kernel(self, key: str) -> Optional[CompiledKernel]:
        """Compiled kernel for ``key`` (lazy; None caches the miss, and
        ``miss_reasons[key]`` records why).  A schedule the backend
        cannot lower raises."""
        if key in self._compiled:
            return self._compiled[key]
        func = self._funcs.get(key)
        kern: Optional[CompiledKernel] = None
        if func is None:
            self.miss_reasons[key] = "unknown_key"
        else:
            sch, source, lat = self._schedule_for(key, func)
            if sch is None:
                self.miss_reasons[key] = source
            else:
                lowered = get_backend(self.backend).lower(sch, workload_key=key)
                kern = CompiledKernel(
                    key=key,
                    func=func,
                    fn=jax.jit(lowered.fn),
                    out_name=func.outputs[0].name,
                    source=source,
                    latency_s=lat,
                    meta=lowered.meta,
                )
        self._compiled[key] = kern
        return kern

    def warm(self, keys: Optional[Sequence[str]] = None) -> int:
        """Eagerly compile kernels; returns how many are dispatchable."""
        n = 0
        for k in keys if keys is not None else self.keys():
            n += self.kernel(k) is not None
        return n

    # -- op-level lookups (called from model layers at trace time) ---------

    def _note(
        self,
        outcome: str,
        key: Optional[str],
        site: str,
        reason: Optional[str] = None,
    ) -> None:
        """Record a dispatch outcome ("hit" | "miss" | "fallback") in the
        per-key table and the trace stream.  The legacy
        ``stats``/``hits_by_key`` counters are NOT touched here — callers
        keep incrementing those at the historical points."""
        row_key = key if key else f"site:{site}"
        row = self._by_key.get(row_key)
        if row is None:
            row = self._by_key[row_key] = {
                "site": site,
                "hits": 0,
                "misses": 0,
                "fallbacks": 0,
                "reasons": {},
            }
        row["hits" if outcome == "hit" else
            "misses" if outcome == "miss" else "fallbacks"] += 1
        if reason:
            row["reasons"][reason] = row["reasons"].get(reason, 0) + 1
        if trace_enabled():
            emit(
                f"dispatch.{outcome}",
                key=key,
                site=site,
                reason=reason,
                mode=self.mode,
                backend=self.backend,
            )

    def stats_by_key(self) -> Dict[str, Dict[str, Any]]:
        """Per-key (or per-site for keyless fallbacks) outcome table:
        ``{key: {site, hits, misses, fallbacks, reasons: {reason: n}}}``."""
        return {
            k: {**row, "reasons": dict(row["reasons"])}
            for k, row in self._by_key.items()
        }

    def _lookup(self, key: str, site: str = "") -> Optional[CompiledKernel]:
        kern = self.kernel(key)
        if kern is None:
            self.stats["misses"] += 1
            self._note("miss", key, site, self.miss_reasons.get(key))
            return None
        self.stats["hits"] += 1
        self.hits_by_key[key] = self.hits_by_key.get(key, 0) + 1
        self._note("hit", key, site)
        return kern

    def dense(
        self, x: jnp.ndarray, w: jnp.ndarray, transpose_w: bool = False
    ) -> Optional[jnp.ndarray]:
        """Tuned ``x @ w`` over the last dim of x; None -> caller falls back.

        ``transpose_w=True`` serves a weight stored (n, k) — the
        tied-embedding unembed ``bsd,vd->bsv`` — by transposing at load:
        the same tuned ``dense`` (m, n, k) kernel runs, and the transpose
        folds into the jitted graph (XLA fuses it into the operand read).
        """
        if x.ndim < 1 or w.ndim != 2:
            self._note("fallback", None, "dense", "shape_mismatch")
            return None
        if transpose_w:
            if x.shape[-1] != w.shape[1]:
                self._note("fallback", None, "dense", "shape_mismatch")
                return None
            n, k = int(w.shape[0]), int(w.shape[1])
        else:
            if x.shape[-1] != w.shape[0]:
                self._note("fallback", None, "dense", "shape_mismatch")
                return None
            k, n = int(w.shape[0]), int(w.shape[1])
        m = 1
        for s in x.shape[:-1]:
            m *= int(s)
        mesh = get_mesh()
        if mesh is not None:
            out = self._mesh_dense(x, w, transpose_w, m, n, k, mesh)
            if out is not None:
                return out
        kern = self._lookup(workload_key("dense", m=m, n=n, k=k), "dense")
        if kern is None:
            return None
        if kern.grad_fn is None:
            def ref(x2, w2):
                return jnp.einsum(
                    "mk,kn->mn", x2, w2, preferred_element_type=jnp.float32
                )

            def fwd_kernel(x2, w2):
                return kern.fn({"X": x2, "W": w2})[kern.out_name]

            kern.grad_fn = _with_reference_grad(fwd_kernel, ref)
        x2 = x.reshape(m, k).astype(jnp.float32)
        w2 = w.astype(jnp.float32)
        if transpose_w:
            w2 = w2.T  # (n, k) -> (k, n); VJP flows through the transpose
        out = kern.grad_fn(x2, w2)
        return out.reshape(*x.shape[:-1], n).astype(x.dtype)

    def batch_matmul(
        self, a: jnp.ndarray, b: jnp.ndarray
    ) -> Optional[jnp.ndarray]:
        """Tuned batched ``a @ b``; a: (..., M, K), b: (..., K, N) with
        identical leading (batch) dims.  Returns float32 (the workload's
        accumulate dtype — callers like online-softmax attention need the
        f32 scores); None -> caller falls back to its jnp einsum.
        """
        if a.ndim < 3 or b.ndim != a.ndim or a.shape[-1] != b.shape[-2]:
            self._note("fallback", None, "batch_matmul", "shape_mismatch")
            return None
        if a.shape[:-2] != b.shape[:-2]:
            self._note("fallback", None, "batch_matmul", "shape_mismatch")
            return None
        bdims = a.shape[:-2]
        B = 1
        for s in bdims:
            B *= int(s)
        M, K = int(a.shape[-2]), int(a.shape[-1])
        N = int(b.shape[-1])
        mesh = get_mesh()
        if mesh is not None:
            out = self._mesh_batch_matmul(a, b, B, M, N, K, bdims, mesh)
            if out is not None:
                return out
        kern = self._lookup(
            workload_key("batch_matmul", b=B, m=M, n=N, k=K), "batch_matmul"
        )
        if kern is None:
            return None
        if kern.grad_fn is None:
            def ref(a2, b2):
                return jnp.einsum(
                    "bmk,bkn->bmn", a2, b2, preferred_element_type=jnp.float32
                )

            def fwd_kernel(a2, b2):
                return kern.fn({"A": a2, "B": b2})[kern.out_name]

            kern.grad_fn = _with_reference_grad(fwd_kernel, ref)
        a2 = a.reshape(B, M, K).astype(jnp.float32)
        b2 = b.reshape(B, K, N).astype(jnp.float32)
        out = kern.grad_fn(a2, b2)
        return out.reshape(*bdims, M, N)

    def attention(
        self,
        q: jnp.ndarray,
        k: jnp.ndarray,
        v: jnp.ndarray,
        *,
        causal: bool = True,
        window: Optional[Any] = None,
        softcap: Optional[float] = None,
        scale: Optional[float] = None,
        q_offset: int = 0,
    ) -> Optional[jnp.ndarray]:
        """Fused attention with database-tuned ``(block_q, block_kv)``.

        Lookup order: (1) a tuned ``attention`` workload record keyed by
        ``(b, h, kvh, s, d, causal, window, softcap)`` — the backend
        lowers the db-best trace, so the blocks are the search's, not a
        hardcoded default; (2) the backend's default fused path (the
        pre-tuning fixed blocks), when it serves one.

        Only static configurations are fusable: a traced ``window`` (the
        per-layer scan metadata) or a nonzero ``q_offset`` (decode) falls
        back to the layer's chunked online-softmax path.  Backward runs
        the reference-attention VJP, like every other dispatched kernel.
        """
        if isinstance(q_offset, jax.core.Tracer) or q_offset != 0:
            self._note("fallback", None, "attention", "decode_offset")
            return None
        B, H, S, D = (int(s) for s in q.shape)
        KVH, T = int(k.shape[1]), int(k.shape[2])
        if v.shape != k.shape or T != S or H % KVH != 0:
            self._note("fallback", None, "attention", "shape_mismatch")
            return None
        if window is not None:
            if isinstance(window, jax.core.Tracer):
                self._note("fallback", None, "attention", "traced_window")
                return None
            w = int(window)
            # 0 = global; a window covering the whole sequence is global
            # too — the canonical form the extracted task keys use
            window = None if (w <= 0 or w >= S) else w
        if softcap is not None and isinstance(softcap, jax.core.Tracer):
            self._note("fallback", None, "attention", "traced_softcap")
            return None

        def ref(q2, k2, v2):
            from ..kernels import ref as kref

            return kref.flash_attention(
                q2, k2, v2, causal=causal, window=window, softcap=softcap,
                scale=scale,
            )

        # (1) tuned workload record — only the workload's own scale (the
        # 1/sqrt(d) every model path uses) and causal windows are keyed
        default_scale = scale is None or abs(scale - D**-0.5) < 1e-12
        if default_scale and not (window is not None and not causal):
            mesh = get_mesh()
            if mesh is not None:
                out = self._mesh_attention(
                    q, k, v, B, H, KVH, S, D,
                    causal=causal, window=window, softcap=softcap,
                    ref=ref, mesh=mesh,
                )
                if out is not None:
                    return out
            key = workload_key(
                "attention", b=B, h=H, kvh=KVH, s=S, d=D,
                causal=int(bool(causal)), window=int(window or 0),
                softcap=float(softcap or 0.0),
            )
            kern = self.kernel(key)
            unservable = kern is not None and not _attention_kern_servable(
                kern, B, H, S
            )
            if unservable:
                kern = None  # structural lowering too large to serve
            if kern is None:
                self.stats["misses"] += 1
                self._note(
                    "miss",
                    key,
                    "attention",
                    "unservable" if unservable
                    else self.miss_reasons.get(key),
                )
            else:
                self.stats["hits"] += 1
                self.hits_by_key[key] = self.hits_by_key.get(key, 0) + 1
                self._note("hit", key, "attention")
                G = H // KVH
                if kern.grad_fn is None:
                    def fwd_kernel(q5, k2, v2):
                        return kern.fn({"Q": q5, "K": k2, "V": v2})[
                            kern.out_name
                        ]

                    def ref5(q5, k2, v2):
                        out = ref(q5.reshape(B, H, S, D), k2, v2)
                        return out.reshape(B, KVH, G, S, D)

                    kern.grad_fn = _with_reference_grad(fwd_kernel, ref5)
                self.stats["attention_tuned"] += 1
                q5 = q.reshape(B, KVH, G, S, D).astype(jnp.float32)
                out = kern.grad_fn(
                    q5, k.astype(jnp.float32), v.astype(jnp.float32)
                )
                return out.reshape(B, H, S, D).astype(q.dtype)

        # (2) backend default fused path (fixed pre-tuning blocks)
        be = get_backend(self.backend)
        fused = getattr(be, "fused_attention", None)
        if fused is None:
            self._note("fallback", None, "attention", "no_fused_backend")
            return None

        def kernel_fn(q2, k2, v2):
            # block sizes are the backend's concern here: it picks/snaps
            # its own default tiles for untuned shapes
            return fused(
                q2, k2, v2, causal=causal, window=window, softcap=softcap,
                scale=scale,
            )

        self.stats["attention_fused"] += 1
        self._note("fallback", None, "attention", "backend_fused")
        return _with_reference_grad(kernel_fn, ref)(q, k, v)

    def decode_attention(
        self,
        q: jnp.ndarray,  # (B, H, 1, D)
        k: jnp.ndarray,  # (B, KVH, T, D) — full fixed-shape cache
        v: jnp.ndarray,
        *,
        length: Any,  # traced valid length: scalar or per-slot (B,)
        window: Optional[Any] = None,
        softcap: Optional[float] = None,
        scale: Optional[float] = None,
    ) -> Optional[jnp.ndarray]:
        """Tuned single-token decode attention (serving).

        Serves ``attention_decode`` records keyed by the *static* shape
        ``(b, h, kvh, t, d, softcap)`` — ``t`` is the fixed cache length,
        so the key is position-independent.  The dynamic part of decode
        (traced per-slot lengths, the layer's static window) folds into an
        additive bias computed as data at call time and fed to the kernel
        as the workload's BIAS input: one tuned kernel serves every decode
        step of a continuous-batching arena, which is what finally lets
        nonzero-position attention dispatch instead of falling back.
        """
        B, H, S, D = (int(s) for s in q.shape)
        KVH, T = int(k.shape[1]), int(k.shape[2])
        if S != 1:
            # in-tick prefill chunk: a (B, C) serve_step tick runs its
            # chunk queries through the reference staircase path; only
            # single-token decode has a tuned kernel shape
            self._note("fallback", None, "attention_decode", "chunked_query")
            return None
        if v.shape != k.shape or H % KVH != 0:
            self._note("fallback", None, "attention_decode", "shape_mismatch")
            return None
        if isinstance(window, jax.core.Tracer):
            self._note("fallback", None, "attention_decode", "traced_window")
            return None
        if softcap is not None and isinstance(softcap, jax.core.Tracer):
            self._note("fallback", None, "attention_decode", "traced_softcap")
            return None
        if scale is not None and abs(scale - D**-0.5) > 1e-12:
            self._note(
                "fallback", None, "attention_decode", "nondefault_scale"
            )
            return None
        key = workload_key(
            "attention_decode", b=B, h=H, kvh=KVH, t=T, d=D,
            softcap=float(softcap or 0.0),
        )
        kern = self._lookup(key, "attention_decode")
        if kern is None:
            return None
        G = H // KVH
        w = int(window or 0)
        # mask as data: 0 where attendable, -1e30 where not.  Matches the
        # reference exactly — position < length, and inside the window
        # when the layer is local (ring wraparound approximated by slot,
        # like the reference path).
        pos = jnp.arange(T)
        lv = jnp.broadcast_to(jnp.asarray(length), (B,))
        valid = pos[None, :] < lv[:, None]
        if w > 0:
            valid = valid & (pos[None, :] > lv[:, None] - 1 - w)
        bias = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)
        if kern.grad_fn is None:
            scale_v = D**-0.5

            def fwd_kernel(q4, k2, v2, b2):
                return kern.fn({"Q": q4, "K": k2, "V": v2, "BIAS": b2})[
                    kern.out_name
                ]

            def ref(q4, k2, v2, b2):
                s = jnp.einsum(
                    "bkgd,bktd->bkgt", q4, k2,
                    preferred_element_type=jnp.float32,
                ) * scale_v
                if softcap:
                    s = softcap * jnp.tanh(s / softcap)
                s = s + b2[:, None, None, :]
                p = jax.nn.softmax(s, axis=-1)
                return jnp.einsum("bkgt,bktd->bkgd", p, v2)

            kern.grad_fn = _with_reference_grad(fwd_kernel, ref)
        self.stats["attention_decode_tuned"] += 1
        q4 = q.reshape(B, KVH, G, D).astype(jnp.float32)
        out = kern.grad_fn(
            q4, k.astype(jnp.float32), v.astype(jnp.float32), bias
        )
        return out.reshape(B, H, 1, D).astype(q.dtype)

    def rmsnorm(
        self, x: jnp.ndarray, w: jnp.ndarray, eps: float
    ) -> Optional[jnp.ndarray]:
        """Tuned RMS norm over the last axis; None -> caller falls back."""
        if x.ndim < 1 or w.ndim != 1 or x.shape[-1] != w.shape[0]:
            self._note("fallback", None, "rmsnorm", "shape_mismatch")
            return None
        tokens = 1
        for s in x.shape[:-1]:
            tokens *= int(s)
        d = int(x.shape[-1])
        kern = self._lookup(
            workload_key("rmsnorm", d=d, eps=eps, tokens=tokens), "rmsnorm"
        )
        if kern is None:
            return None
        if kern.grad_fn is None:
            def ref(x2, w2):
                var = jnp.mean(x2 * x2, axis=-1, keepdims=True)
                return x2 * jax.lax.rsqrt(var + eps) * w2

            def fwd_kernel(x2, w2):
                return kern.fn({"X": x2, "W": w2})[kern.out_name]

            kern.grad_fn = _with_reference_grad(fwd_kernel, ref)
        x2 = x.reshape(tokens, d).astype(jnp.float32)
        out = kern.grad_fn(x2, w.astype(jnp.float32))
        return out.reshape(x.shape).astype(x.dtype)

    # -- mesh-aware dispatch (shard_map-served per-shard kernels) -----------

    def _mesh_kernel(self, op: str, kwargs: Dict[str, Any], mesh):
        """(kernel, ShardedWorkload, key) for the per-shard shape of one
        call under ``mesh``, or ``(None, sw, key)`` when the per-shard key
        has no servable record (caller falls through to the global path).
        The per-shard shape comes from the same
        :func:`~repro.distributed.sharding.shard_workload` rule task
        extraction uses, so tuned-under-mesh keys always line up."""
        sw = shard_workload(op, kwargs, mesh)
        if sw is None:
            return None, None, None
        key = workload_key(op, **sw.kwargs)
        kern = self.kernel(key)
        if kern is None:
            self._note("fallback", key, op, "no_shard_record")
            return None, sw, key
        return kern, sw, key

    def _mesh_hit(self, key: str, site: str) -> None:
        self.stats["hits"] += 1
        self.stats["mesh_sharded"] += 1
        self.hits_by_key[key] = self.hits_by_key.get(key, 0) + 1
        self._note("hit", key, site, "mesh_shard")

    def _mesh_wrap(self, kern: CompiledKernel, mesh, build: Callable):
        """Cache the shard_map-wrapped grad fn per kernel (rebuilt only if
        a different mesh shows up)."""
        if kern.mesh_grad_fn is None or kern.mesh_grad_fn[0] is not mesh:
            kern.mesh_grad_fn = (mesh, build())
        return kern.mesh_grad_fn[1]

    def _mesh_dense(
        self, x: jnp.ndarray, w: jnp.ndarray, transpose_w: bool,
        m: int, n: int, k: int, mesh,
    ) -> Optional[jnp.ndarray]:
        """Serve the per-shard tuned dense kernel inside shard_map:
        rows split over data-parallel axes, columns over the model axis,
        contraction whole — each shard computes an exact local tile."""
        kern, sw, key = self._mesh_kernel("dense", {"m": m, "n": n, "k": k}, mesh)
        if kern is None:
            return None
        m_ax = sw.dim_axes.get("m")
        n_ax = sw.dim_axes.get("n")

        def build():
            x_spec = P(m_ax, None)
            w_spec = P(None, n_ax)
            o_spec = P(m_ax, n_ax)

            def body(x2, w2):
                return kern.fn({"X": x2, "W": w2})[kern.out_name]

            fwd = shard_map(
                body, mesh=mesh, in_specs=(x_spec, w_spec),
                out_specs=o_spec, check_vma=False,
            )

            def ref(x2, w2):
                return jnp.einsum(
                    "mk,kn->mn", x2, w2, preferred_element_type=jnp.float32
                )

            return _with_reference_grad(fwd, ref)

        grad_fn = self._mesh_wrap(kern, mesh, build)
        x2 = x.reshape(m, k).astype(jnp.float32)
        w2 = w.astype(jnp.float32)
        if transpose_w:
            w2 = w2.T
        out = grad_fn(x2, w2)
        self._mesh_hit(key, "dense")
        return out.reshape(*x.shape[:-1], n).astype(x.dtype)

    def _mesh_batch_matmul(
        self, a: jnp.ndarray, b: jnp.ndarray,
        B: int, M: int, N: int, K: int, bdims, mesh,
    ) -> Optional[jnp.ndarray]:
        """Per-shard tuned batch_matmul under shard_map: the batch dim
        (heads/experts) splits over model, else data-parallel, axes."""
        kern, sw, key = self._mesh_kernel(
            "batch_matmul", {"b": B, "m": M, "n": N, "k": K}, mesh
        )
        if kern is None:
            return None
        b_ax = sw.dim_axes.get("b")

        def build():
            spec = P(b_ax, None, None)

            def body(a2, b2):
                return kern.fn({"A": a2, "B": b2})[kern.out_name]

            fwd = shard_map(
                body, mesh=mesh, in_specs=(spec, spec),
                out_specs=spec, check_vma=False,
            )

            def ref(a2, b2):
                return jnp.einsum(
                    "bmk,bkn->bmn", a2, b2, preferred_element_type=jnp.float32
                )

            return _with_reference_grad(fwd, ref)

        grad_fn = self._mesh_wrap(kern, mesh, build)
        a2 = a.reshape(B, M, K).astype(jnp.float32)
        b2 = b.reshape(B, K, N).astype(jnp.float32)
        out = grad_fn(a2, b2)
        self._mesh_hit(key, "batch_matmul")
        return out.reshape(*bdims, M, N)

    def _mesh_attention(
        self, q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
        B: int, H: int, KVH: int, S: int, D: int,
        *, causal, window, softcap, ref: Callable, mesh,
    ) -> Optional[jnp.ndarray]:
        """Per-shard tuned fused attention under shard_map: heads split
        over the model axis (q and kv heads together, so each shard keeps
        whole GQA groups), batch over data-parallel axes.  The sequence
        dim stays whole — causal/window masking is position-exact."""
        kern, sw, key = self._mesh_kernel(
            "attention",
            {
                "b": B, "h": H, "kvh": KVH, "s": S, "d": D,
                "causal": int(bool(causal)), "window": int(window or 0),
                "softcap": float(softcap or 0.0),
            },
            mesh,
        )
        if kern is None:
            return None
        if not _attention_kern_servable(
            kern, sw.kwargs["b"], sw.kwargs["h"], S
        ):
            self._note("fallback", key, "attention", "unservable")
            return None
        b_ax = sw.dim_axes.get("b")
        h_ax = sw.dim_axes.get("h")
        G = H // KVH

        def build():
            q_spec = P(b_ax, h_ax, None, None, None)  # (B, KVH, G, S, D)
            kv_spec = P(b_ax, h_ax, None, None)       # (B, KVH, S, D)

            def body(q5, k2, v2):
                return kern.fn({"Q": q5, "K": k2, "V": v2})[kern.out_name]

            fwd = shard_map(
                body, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                out_specs=q_spec, check_vma=False,
            )

            def ref5(q5, k2, v2):
                out = ref(q5.reshape(B, H, S, D), k2, v2)
                return out.reshape(B, KVH, G, S, D)

            return _with_reference_grad(fwd, ref5)

        grad_fn = self._mesh_wrap(kern, mesh, build)
        q5 = q.reshape(B, KVH, G, S, D).astype(jnp.float32)
        out = grad_fn(q5, k.astype(jnp.float32), v.astype(jnp.float32))
        self._mesh_hit(key, "attention")
        self.stats["attention_tuned"] += 1
        return out.reshape(B, H, S, D).astype(q.dtype)


# A structurally-lowered (non-fused) attention kernel materializes the
# (b, h, s, s) score/softmax buffers the chunked online-softmax path
# exists to avoid; serve it only while that footprint stays modest.  The
# fused flash lowering streams kv blocks and has no such limit.
MAX_STRUCTURAL_ATTN_SCORE_BYTES = 256 << 20


def _attention_kern_servable(
    kern: CompiledKernel, b: int, h: int, s: int
) -> bool:
    if kern.meta and kern.meta.get("pallas_kernel") == "flash_attention":
        return True
    return 4 * b * h * s * s <= MAX_STRUCTURAL_ATTN_SCORE_BYTES


def _with_reference_grad(kernel_fn: Callable, ref_fn: Callable) -> Callable:
    """Forward through the tuned kernel, backward through the reference VJP."""

    @jax.custom_vjp
    def f(*args):
        return kernel_fn(*args)

    def fwd(*args):
        return kernel_fn(*args), args

    def bwd(args, g):
        _, vjp = jax.vjp(ref_fn, *args)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def maybe_dispatch(ctx: Optional[DispatchContext]):
    """``with maybe_dispatch(ctx):`` — no-op when ctx is None."""
    from contextlib import nullcontext

    return ctx if ctx is not None else nullcontext()
