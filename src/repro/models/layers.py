"""Shared pure-JAX building blocks for the model zoo.

Parameters are nested dicts of jnp arrays; every creation site also
registers *logical axis names* so the distribution layer can map them to
mesh axes (see ``distributed/sharding.py``).  Attention uses a chunked
online-softmax scan (flash-attention in jnp) so long-context activations
never materialize S×S scores — the Pallas kernel in ``kernels/`` is the
TPU-native counterpart.
"""

from __future__ import annotations

import math
import sys
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


# ---------------------------------------------------------------------------
# Tuned-kernel dispatch hook
# ---------------------------------------------------------------------------


def _dispatch_ctx():
    """Active ``repro.integration.dispatch.DispatchContext``, or None.

    Read through ``sys.modules`` instead of an import: a context can only
    be active if the integration module is already imported, and this
    keeps the model layers import-light and cycle-free.
    """
    mod = sys.modules.get("repro.integration.dispatch")
    return mod.current() if mod is not None else None


def _attn_recorder():
    """Active attention-site recorder (task extraction), or None.

    Same ``sys.modules`` pattern as :func:`_dispatch_ctx`: a recorder can
    only be active while ``repro.integration.extract`` traces the model.
    """
    mod = sys.modules.get("repro.integration.extract")
    return mod.current_attention_recorder() if mod is not None else None


def dense_op(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Last-dim contraction ``x @ w`` — the tuned-kernel dispatch point.

    Under an active DispatchContext whose database holds a tuned trace for
    this (m, n, k), the search's best schedule executes here; otherwise
    (no context, no record, shape mismatch) the jnp reference runs.
    Dispatch resolves at trace time: shapes are static under jit.
    """
    ctx = _dispatch_ctx()
    if ctx is not None:
        out = ctx.dense(x, w)
        if out is not None:
            return out
    return jnp.einsum("...d,df->...f", x, w)


def bmm_op(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Batched matmul ``a @ b`` — the batched dispatch point.

    a: (..., M, K), b: (..., K, N), identical leading batch dims; returns
    float32 (accumulate dtype — the attention online-softmax needs f32
    scores).  Under an active DispatchContext with a tuned
    ``batch_matmul`` record for this (B, M, N, K), the tuned kernel
    executes; otherwise the jnp einsum reference runs.  The attention
    score/value contractions and MoE expert FFNs call through here.
    """
    ctx = _dispatch_ctx()
    if ctx is not None:
        out = ctx.batch_matmul(a, b)
        if out is not None:
            return out
    return jnp.einsum(
        "...mk,...kn->...mn", a, b, preferred_element_type=jnp.float32
    )


def grouped_op(
    x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray
) -> jnp.ndarray:
    """Grouped matmul — the grouped-expert dispatch point.

    x: (m, k) rows sorted by group, w: (g, k, n), group_sizes: (g,) int32
    summing to m; row block e (``group_sizes[e]`` rows, in order) is
    multiplied by ``w[e]``.  Returns (m, n) in x's dtype, accumulated in
    float32.  Under an active DispatchContext with a tuned
    ``grouped_dense`` record for (m, g, k, n, dtype) the tuned kernel
    runs; otherwise ``jax.lax.ragged_dot``, XLA's own grouped matmul.
    """
    ctx = _dispatch_ctx()
    if ctx is not None:
        out = ctx.grouped_dense(x, w, group_sizes)
        if out is not None:
            return out
    return jax.lax.ragged_dot(
        x, w, group_sizes, preferred_element_type=jnp.float32
    ).astype(x.dtype)


# logical-axis registry: path-pattern -> axes tuple, filled by init fns.
# (simpler than threading metadata through every pytree leaf)
PARAM_AXES: Dict[str, Tuple[Optional[str], ...]] = {}


def reg_axes(name: str, axes: Tuple[Optional[str], ...]) -> None:
    PARAM_AXES[name] = axes


def _init(rng, shape, scale=None, dtype=jnp.bfloat16):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(rng, shape, dtype=jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, name: str) -> jnp.ndarray:
    reg_axes(name, ("embed",))
    return jnp.ones((d,), dtype=jnp.float32)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    ctx = _dispatch_ctx()
    if ctx is not None:
        out = ctx.rmsnorm(x, w, eps)
        if out is not None:
            return out
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0):
    """x: (..., S, D); positions: (..., S) int32."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray, positions_3d: jnp.ndarray, sections=(16, 24, 24),
    theta: float = 10000.0,
):
    """Qwen2-VL multimodal RoPE: positions_3d (..., S, 3) = (t, h, w) ids.

    The head_dim/2 frequency slots are partitioned into (temporal, height,
    width) sections; text tokens carry identical t/h/w ids, which reduces to
    standard RoPE.
    """
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)  # (D/2,)
    n = D // 2
    sec = np.asarray(sections, dtype=np.int64)
    sec = (sec * n // sec.sum()).tolist()
    sec[-1] = n - sum(sec[:-1])
    sel = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sec)]
    )  # (D/2,) in {0,1,2}
    # gather per-frequency position channel:
    # positions_3d (..., S, 3) -> (..., S, D/2) selecting channel sel[f]
    p = jnp.moveaxis(positions_3d, -1, 0)  # (3, ..., S)
    pos = p[sel]  # (D/2, ..., S) via fancy index on axis 0
    pos = jnp.moveaxis(pos, 0, -1)  # (..., S, D/2)
    ang = pos.astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked flash attention (pure jnp, scan over KV blocks)
# ---------------------------------------------------------------------------


def chunked_attention(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, KVH, T, D)
    v: jnp.ndarray,  # (B, KVH, T, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    chunk: int = 1024,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Online-softmax attention, O(S·chunk) memory.  GQA folded via repeat
    of the *sharded* head dim (no global materialization under GSPMD).

    Two tuned-kernel dispatch points: under an active DispatchContext the
    whole call may swap to the backend's fused flash-attention kernel
    (static window/offset only), and otherwise the score and value
    contractions route through :func:`bmm_op` so tuned ``batch_matmul``
    records swap into the online-softmax scan."""
    rec = _attn_recorder()
    if rec is not None:
        rec.add(
            q_shape=tuple(q.shape), kvh=int(k.shape[1]), kv_seq=int(k.shape[2]),
            causal=causal, window=window, softcap=softcap, scale=scale,
            q_offset=q_offset,
        )
    ctx = _dispatch_ctx()
    if ctx is not None:
        fused = ctx.attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset,
        )
        if fused is not None:
            return fused
    B, H, S, D = q.shape
    KVH, T = k.shape[1], k.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    chunk = min(chunk, T)
    T_valid = T  # un-padded key count: zero-padded positions must mask out
    if T % chunk:
        pad = chunk - T % chunk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        T = T + pad
    nc = T // chunk
    kc = k.reshape(B, KVH, nc, chunk, D).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, KVH, nc, chunk, D).transpose(2, 0, 1, 3, 4)
    q_pos = q_offset + jnp.arange(S)

    qg = q.reshape(B, KVH, G, S, D)
    # (B·KVH, G·S, D): the canonical batched-matmul layout — the same
    # (b, m, k) the task extractor keys the contraction under, so tuned
    # batch_matmul records dispatch through bmm_op
    qf = qg.reshape(B * KVH, G * S, D)

    def step(carry, inp):
        m, l, acc = carry
        ci, kb, vb = inp  # (B,KVH,chunk,D)
        kt = kb.reshape(B * KVH, chunk, D).swapaxes(1, 2)  # (B·KVH, D, chunk)
        s = bmm_op(qf, kt).reshape(B, KVH, G, S, chunk) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((S, chunk), dtype=bool)
        mask = mask & (k_pos[None, :] < T_valid)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            # window may be a traced per-layer scalar; <= 0 means global
            w = jnp.asarray(window)
            mask = mask & ((w <= 0) | (q_pos[:, None] - k_pos[None, :] < w))
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        pv = bmm_op(
            p.reshape(B * KVH, G * S, chunk).astype(vb.dtype),
            vb.reshape(B * KVH, chunk, D),
        ).reshape(B, KVH, G, S, D)
        acc_new = acc * alpha + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KVH, G, S, 1), -1e30, dtype=jnp.float32)
    l0 = jnp.zeros((B, KVH, G, S, 1), dtype=jnp.float32)
    a0 = jnp.zeros((B, KVH, G, S, D), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(nc), kc, vc)
    )
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, H, S, D).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # (B, H, S, D) — S = 1 (decode) or a prefill chunk
    k: jnp.ndarray,  # (B, KVH, T, D) — full cache
    v: jnp.ndarray,
    *,
    length: jnp.ndarray,  # valid cache length: scalar or per-slot (B,)
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Attention against a KV cache (serving decode / chunked prefill).

    ``length`` may be a scalar (legacy engine: every lane at the same
    position) or a per-slot ``(B,)`` vector (continuous-batching arena:
    each slot is at its own position).  It is the valid cache length for
    the *first* query position; when ``S > 1`` (an in-tick prefill chunk
    whose keys were just written to the cache) query ``c`` sees one more
    cache position than query ``c - 1`` — the causal staircase of a
    chunk, capped at ``T``.  Under an active DispatchContext the single-
    token case can swap to a tuned ``attention_decode`` kernel: the
    program is static in the cache length ``T`` and the traced per-slot
    lengths enter the kernel as an additive bias, so one tuned kernel
    serves every decode step."""
    B, H, S, D = q.shape
    KVH, T = k.shape[1], k.shape[2]
    G = H // KVH
    rec = _attn_recorder()
    if rec is not None:
        rec.add(
            q_shape=tuple(q.shape), kvh=int(KVH), kv_seq=int(T),
            causal=True, window=window, softcap=softcap, scale=scale,
            q_offset=0, kind="decode",
        )
    ctx = _dispatch_ctx()
    if ctx is not None:
        tuned = ctx.decode_attention(
            q, k, v, length=length, window=window, softcap=softcap,
            scale=scale,
        )
        if tuned is not None:
            return tuned
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    pos = jnp.arange(T)
    lv = jnp.broadcast_to(jnp.asarray(length), (B,))
    if S == 1:
        qg = q.reshape(B, KVH, G, D)
        s = jnp.einsum(
            "bkgd,bktd->bkgt", qg, k, preferred_element_type=jnp.float32
        )
        s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = pos[None, :] < lv[:, None]  # (B, T)
        if window is not None:
            w = jnp.asarray(window)
            mask = mask & ((w <= 0) | (pos[None, :] > lv[:, None] - 1 - w))
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgt,bktd->bkgd", p.astype(v.dtype), v)
        return out.reshape(B, H, 1, D).astype(q.dtype)
    # chunk queries: per-row lengths walk the causal staircase
    qg = q.reshape(B, KVH, G, S, D)
    s = jnp.einsum(
        "bkgcd,bktd->bkgct", qg, k, preferred_element_type=jnp.float32
    )
    s = s * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    lens = jnp.minimum(
        lv[:, None] + jnp.arange(S, dtype=lv.dtype)[None, :], T
    )  # (B, S)
    mask = pos[None, None, :] < lens[:, :, None]  # (B, S, T)
    if window is not None:
        w = jnp.asarray(window)
        mask = mask & (
            (w <= 0) | (pos[None, None, :] > lens[:, :, None] - 1 - w)
        )
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgct,bktd->bkgcd", p.astype(v.dtype), v)
    return out.reshape(B, H, S, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------


def attention_init(rng, cfg, prefix: str) -> Dict:
    D, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": _init(ks[0], (D, H * hd)),
        "wk": _init(ks[1], (D, KVH * hd)),
        "wv": _init(ks[2], (D, KVH * hd)),
        "wo": _init(ks[3], (H * hd, D), scale=1.0 / math.sqrt(H * hd)),
    }
    reg_axes(f"{prefix}/wq", ("embed", "heads"))
    reg_axes(f"{prefix}/wk", ("embed", "heads"))
    reg_axes(f"{prefix}/wv", ("embed", "heads"))
    reg_axes(f"{prefix}/wo", ("heads", "embed"))
    if cfg.attn_gate:
        p["wg"] = _init(jax.random.fold_in(rng, 4), (D, H * hd))
        reg_axes(f"{prefix}/wg", ("embed", "heads"))
    if cfg.qk_norm:
        for name in ("q_norm", "k_norm"):
            p[name] = jnp.ones((hd,), dtype=jnp.float32)
            reg_axes(f"{prefix}/{name}", (None,))
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype=jnp.float32)
        p["bk"] = jnp.zeros((KVH * hd,), dtype=jnp.float32)
        p["bv"] = jnp.zeros((KVH * hd,), dtype=jnp.float32)
        reg_axes(f"{prefix}/bq", ("heads",))
        reg_axes(f"{prefix}/bk", ("heads",))
        reg_axes(f"{prefix}/bv", ("heads",))
    return p


def qkv_proj(p: Dict, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, ...]:
    B, S, _ = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense_op(x, p["wq"])
    k = dense_op(x, p["wk"])
    v = dense_op(x, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, KVH, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, KVH, hd).transpose(0, 2, 1, 3)
    return q, k, v


# ---------------------------------------------------------------------------
# MLP (gated) and MoE
# ---------------------------------------------------------------------------


def mlp_init(rng, d_model: int, d_ff: int, prefix: str, gated: bool = True) -> Dict:
    ks = jax.random.split(rng, 3)
    p = {
        "wi": _init(ks[0], (d_model, d_ff)),
        "wo": _init(ks[1], (d_ff, d_model)),
    }
    reg_axes(f"{prefix}/wi", ("embed", "mlp"))
    reg_axes(f"{prefix}/wo", ("mlp", "embed"))
    if gated:
        p["wg"] = _init(ks[2], (d_model, d_ff))
        reg_axes(f"{prefix}/wg", ("embed", "mlp"))
    return p


def mlp(p: Dict, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    h = dense_op(x, p["wi"])
    actf = {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}[act]
    if "wg" in p:
        h = actf(dense_op(x, p["wg"])) * h
    else:
        h = actf(h)
    return dense_op(h, p["wo"])


def moe_init(rng, cfg, prefix: str) -> Dict:
    D, E, F = cfg.d_model, cfg.moe_experts, cfg.expert_ff
    ks = jax.random.split(rng, 5)
    p = {
        "router": _init(ks[0], (D, E), dtype=jnp.float32),
        "wi": _init(ks[1], (E, D, F)),
        "wg": _init(ks[2], (E, D, F)),
        "wo": _init(ks[3], (E, F, D), scale=1.0 / math.sqrt(F)),
    }
    reg_axes(f"{prefix}/router", ("embed", None))
    reg_axes(f"{prefix}/wi", ("experts", "embed", None))
    reg_axes(f"{prefix}/wg", ("experts", "embed", None))
    reg_axes(f"{prefix}/wo", ("experts", None, "embed"))
    if cfg.moe_router == "sigmoid":
        p["expert_bias"] = jnp.zeros((E,), dtype=jnp.float32)
        reg_axes(f"{prefix}/expert_bias", (None,))
    if cfg.moe_shared_experts:
        p["shared"] = mlp_init(
            ks[4], D, F * cfg.moe_shared_experts, f"{prefix}/shared"
        )
    return p


_ACT = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}


def moe_route(
    p: Dict, xt: jnp.ndarray, top_k: int, router: str = "softmax",
    scale: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert ids (T, k) and float32 weights (T, k) of tokens ``xt`` (T, D).

    ``softmax``: top-k of the softmax, renormalised.  ``sigmoid``: scores
    ``s = sigmoid(x W_r)`` in float32; experts are chosen by top-k of
    ``s + expert_bias`` (the bias steers selection only), weighted by
    their own scores normalised to sum 1 and times ``scale``.
    """
    if router == "sigmoid":
        logits = jnp.einsum(
            "td,de->te", xt.astype(jnp.float32), p["router"],
            precision=jax.lax.Precision.HIGHEST,
        )
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + p["expert_bias"], top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    return idx, w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)


def moe(p: Dict, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """Top-k mixture of experts (plus the shared experts, if any).

    ``cfg.moe_capacity_factor <= 0`` is dropless: every (token, slot)
    pair is computed, by grouped matmuls over the pairs sorted by expert
    (:func:`_moe_grouped`).  Otherwise, and on a device mesh, tokens go
    through the capacity-bounded scatter (:func:`_moe_capacity`), whose
    scatter/gather pair becomes the all-to-all under expert parallelism.
    """
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("moe.route"):
        idx, w = moe_route(
            p, xt, cfg.moe_top_k, cfg.moe_router, cfg.moe_route_scale
        )
    from ..distributed import sharding as _shd

    if cfg.moe_capacity_factor <= 0 and _shd.get_mesh() is None:
        out = _moe_grouped(p, xt, idx, w, _ACT[cfg.act])
    else:
        out = _moe_capacity(p, xt, idx, w, cfg.moe_capacity_factor, _ACT[cfg.act])
    if "shared" in p:
        out = out + mlp(p["shared"], x, cfg.act).reshape(B * S, D)
    return out.reshape(B, S, D).astype(x.dtype)


def _moe_grouped(p: Dict, xt, idx, w, actf) -> jnp.ndarray:
    """Dropless experts: the T*k (token, slot) rows sorted by expert, the
    experts' up/gate and down projections as grouped matmuls over them
    (:func:`grouped_op`), then unsorted and weighted per token (float32
    combine)."""
    T, D = xt.shape
    k = idx.shape[1]
    E = p["wi"].shape[0]
    with jax.named_scope("moe.permute"):
        eid = idx.reshape(T * k)
        order = jnp.argsort(eid, stable=True)
        rows = xt[order // k]
        sizes = jnp.bincount(eid, length=E).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        h = grouped_op(rows, p["wi"], sizes)
        g = grouped_op(rows, p["wg"], sizes)
        y = grouped_op(actf(g) * h, p["wo"], sizes)
    with jax.named_scope("moe.combine"):
        slot = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype)
        )
        y = y[slot].reshape(T, k, D).astype(jnp.float32)
        return jnp.sum(y * w[..., None], axis=1)


def _moe_capacity(p: Dict, xt, gate_idx, gate_vals, capacity_factor, actf):
    """Capacity-bounded top-k experts with scatter dispatch (GShard-style).

    Tokens are routed to experts through a position-in-expert cumsum and a
    scatter into an (E, C, D) buffer — the scatter/gather pair becomes the
    all-to-all under expert-parallel sharding.  Overflow tokens are dropped
    (their contribution is zero), standard for capacity-based MoE.
    ``capacity_factor <= 0`` (dropless, on a mesh) takes C = T.
    """
    T, D = xt.shape
    top_k = gate_idx.shape[1]
    E = p["router"].shape[1]
    if capacity_factor <= 0:
        C = T
    else:
        C = max(int(capacity_factor * top_k * T / E), 4)
    # position of each (token, k) slot within its expert queue
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (T, k, E)
    flat = onehot.reshape(T * top_k, E)
    pos_in_e = jnp.cumsum(flat, axis=0) - flat  # (T*k, E)
    pos = (pos_in_e * flat).sum(-1)  # (T*k,)
    eid = gate_idx.reshape(T * top_k)
    keep = pos < C
    # scatter tokens into (E, C, D); dropped tokens get an out-of-bounds
    # expert id so mode="drop" skips them (never clobber a live slot)
    buf = jnp.zeros((E, C, D), dtype=xt.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), top_k)
    src = xt[tok_idx]  # (T*k, D)
    e_sc = jnp.where(keep, eid, E)       # E = out of bounds -> dropped
    p_sc = jnp.where(keep, pos, C)
    w_sc = jnp.where(keep, gate_vals.reshape(T * top_k), 0.0)
    # sharding: token rows stay data-parallel, expert buffers expert-parallel
    # -> the scatter/gather pair partitions into an all-to-all instead of a
    # replicated scatter (EXPERIMENTS.md §Perf iter 4: 2.1e12B -> a2a)
    from ..distributed import sharding as _shd

    src = _shd.shard(src, "tokens")
    buf = _shd.shard(buf.at[e_sc, p_sc].set(src, mode="drop"), "experts")
    # expert FFN on (E, C, D) — batched matmuls in canonical layout, so
    # tuned batch_matmul records dispatch through bmm_op (f32 accumulate,
    # cast back to the activation dtype as before)
    h = bmm_op(buf, p["wi"]).astype(buf.dtype)
    g = bmm_op(buf, p["wg"]).astype(buf.dtype)
    h = actf(g) * h
    out_e = _shd.shard(
        bmm_op(h, p["wo"]).astype(buf.dtype), "experts"
    )  # (E, C, D)
    # gather back + weight
    gathered = out_e[e_sc, p_sc]  # (T*k, D)
    gathered = _shd.shard(gathered, "tokens")
    gathered = gathered * w_sc[:, None].astype(gathered.dtype)
    # combine in f32 (iter 5 measured bf16 combine: no collective change —
    # the EP-combine all-reduce is internal to the gather lowering — so keep
    # the numerically safer accumulate)
    out = jnp.zeros((T, D), dtype=jnp.float32)
    return _shd.shard(out.at[tok_idx].add(gathered.astype(jnp.float32)), "tokens")


# ---------------------------------------------------------------------------
# Mamba-2 SSD mixer
# ---------------------------------------------------------------------------


def ssd_init(rng, cfg, prefix: str) -> Dict:
    D = cfg.d_model
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    inner = H * P
    ks = jax.random.split(rng, 6)
    p = {
        "wx": _init(ks[0], (D, inner)),
        "wz": _init(ks[1], (D, inner)),
        "wB": _init(ks[2], (D, N)),
        "wC": _init(ks[3], (D, N)),
        "wdt": _init(ks[4], (D, H), dtype=jnp.float32),
        "A_log": jnp.zeros((H,), dtype=jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, dtype=jnp.float32),
        "wo": _init(ks[5], (inner, D), scale=1.0 / math.sqrt(inner)),
    }
    reg_axes(f"{prefix}/wx", ("embed", "heads"))
    reg_axes(f"{prefix}/wz", ("embed", "heads"))
    reg_axes(f"{prefix}/wB", ("embed", None))
    reg_axes(f"{prefix}/wC", ("embed", None))
    reg_axes(f"{prefix}/wdt", ("embed", None))
    reg_axes(f"{prefix}/A_log", (None,))
    reg_axes(f"{prefix}/dt_bias", (None,))
    reg_axes(f"{prefix}/wo", ("heads", "embed"))
    return p


def _ssd_common(p: Dict, x: jnp.ndarray, cfg):
    B, S, D = x.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    xv = jnp.einsum("bsd,di->bsi", x, p["wx"]).reshape(B, S, H, P)
    z = jnp.einsum("bsd,di->bsi", x, p["wz"]).reshape(B, S, H, P)
    Bm = jnp.einsum("bsd,dn->bsn", x, p["wB"])
    Cm = jnp.einsum("bsd,dn->bsn", x, p["wC"])
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32), p["wdt"]) + p["dt_bias"]
    )
    log_a = -jnp.exp(p["A_log"])[None, None, :] * dt  # (B,S,H), negative
    xin = xv * dt[..., None].astype(xv.dtype)  # ZOH-ish input scaling
    return xin, z, Bm, Cm, log_a


def ssd_mixer(p: Dict, x: jnp.ndarray, cfg, chunk: int = 64) -> jnp.ndarray:
    """Mamba-2 SSD sequence mixer (training / prefill path)."""
    from ..kernels import ref as kref

    B, S, D = x.shape
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    xin, z, Bm, Cm, log_a = _ssd_common(p, x, cfg)
    y = kref.ssd_chunked(xin, log_a, Bm, Cm, chunk=min(chunk, S))
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    return jnp.einsum("bsi,id->bsd", y.reshape(B, S, H * P), p["wo"])


def ssd_mixer_with_state(p: Dict, x: jnp.ndarray, cfg, chunk: int = 64):
    """Like :func:`ssd_mixer` but also returns the final SSM state
    (B, H, N, P) — the prefill → decode handoff."""
    from ..kernels import ref as kref

    B, S, D = x.shape
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    xin, z, Bm, Cm, log_a = _ssd_common(p, x, cfg)
    y, state = kref.ssd_chunked(
        xin, log_a, Bm, Cm, chunk=min(chunk, S), return_state=True
    )
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    return jnp.einsum("bsi,id->bsd", y.reshape(B, S, H * P), p["wo"]), state


def ssd_decode_step(p: Dict, x: jnp.ndarray, state: jnp.ndarray, cfg):
    """Single-token SSD recurrence.  x: (B, 1, D); state: (B, H, N, P)."""
    B = x.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    xv = jnp.einsum("bsd,di->bsi", x, p["wx"]).reshape(B, H, P)
    z = jnp.einsum("bsd,di->bsi", x, p["wz"]).reshape(B, H, P)
    Bm = jnp.einsum("bsd,dn->bn", x, p["wB"])
    Cm = jnp.einsum("bsd,dn->bn", x, p["wC"])
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bh", x.astype(jnp.float32), p["wdt"]) + p["dt_bias"]
    )
    a = jnp.exp(-jnp.exp(p["A_log"])[None] * dt)  # (B,H)
    xin = (xv * dt[..., None]).astype(jnp.float32)
    state = a[:, :, None, None] * state + Bm[:, None, :, None] * xin[:, :, None, :]
    y = jnp.einsum("bn,bhnp->bhp", Cm.astype(jnp.float32), state)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(B, 1, H * P).astype(x.dtype)
    return jnp.einsum("bsi,id->bsd", y, p["wo"]), state


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_init(rng, vocab: int, d_model: int, name: str = "embed") -> jnp.ndarray:
    reg_axes(name, ("vocab", "embed"))
    # N(0, 1/sqrt(d)): embeds*sqrt(d) ~ N(0,1), tied unembed logits ~ O(1)
    return _init(rng, (vocab, d_model), scale=1.0 / math.sqrt(d_model))


def embed(tokens: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    return table[tokens]


def unembed(x: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Tied-embedding unembed ``bsd,vd->bsv`` — a transposed-weight
    dispatch point: the table is stored (vocab, d), so a tuned ``dense``
    record for (m, n=vocab, k=d) serves it via transpose-at-load."""
    ctx = _dispatch_ctx()
    if ctx is not None:
        out = ctx.dense(x, table, transpose_w=True)
        if out is not None:
            return out
    return jnp.einsum("bsd,vd->bsv", x, table)
