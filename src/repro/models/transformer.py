"""Unified decoder LM covering all assigned families.

One scanned-layer decoder implementation parameterized by
:class:`~repro.configs.base.ModelConfig`:

* dense GQA transformers (stablelm / qwen1.5-110b / smollm),
* gemma-2 (local/global alternation, softcaps, sandwich norms),
* MoE (olmoe; arctic with dense-residual MLP),
* Mamba-2 SSD (attention-free),
* Hymba (parallel attention + SSD heads, sliding window),
* Qwen2-VL backbone (M-RoPE, embedding inputs),
* Whisper (encoder stack + cross-attention decoder),
* Trinity (afmoe: leading dense layers, then sparse experts with a
  shared expert, under a 3:1 window/full attention pattern).

Layers are stacked along a leading L axis and executed with
``jax.lax.scan`` so the lowered HLO is O(1) in depth (MaxText-style) —
this keeps 512-device dry-run compiles tractable and is also what you
deploy.  Layers of different kinds (leading dense layers before expert
layers) form stacks of their own, each its own scan, in order.  Within
a stack, a periodic window pattern reaches each layer as a static int;
an aperiodic one (hymba) rides through the scan as a traced (L,)
metadata array.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..distributed import sharding as shd
from . import layers as L

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _layer_init(cfg: ModelConfig, rng, dense: bool = False) -> Dict:
    ks = jax.random.split(rng, 8)
    p: Dict[str, Any] = {"ln1": L.rmsnorm_init(cfg.d_model, "ln")}
    if not cfg.attn_free:
        p["attn"] = L.attention_init(ks[0], cfg, "attn")
        if cfg.post_norms:
            p["post_ln1"] = L.rmsnorm_init(cfg.d_model, "ln")
    if cfg.ssm_state:
        p["ssd"] = L.ssd_init(ks[1], cfg, "ssd")
    if cfg.d_ff:
        p["ln2"] = L.rmsnorm_init(cfg.d_model, "ln")
        if cfg.moe_experts and not dense:
            p["moe"] = L.moe_init(ks[2], cfg, "moe")
            if cfg.moe_dense_residual:
                p["mlp"] = L.mlp_init(ks[3], cfg.d_model, cfg.d_ff, "mlp")
        else:
            p["mlp"] = L.mlp_init(ks[3], cfg.d_model, cfg.d_ff, "mlp")
        if cfg.post_norms:
            p["post_ln2"] = L.rmsnorm_init(cfg.d_model, "ln")
    if cfg.enc_layers:  # decoder cross-attention (whisper)
        p["ln_x"] = L.rmsnorm_init(cfg.d_model, "ln")
        p["xattn"] = L.attention_init(ks[4], cfg, "attn")
    return p


def _enc_layer_init(cfg: ModelConfig, rng) -> Dict:
    ks = jax.random.split(rng, 2)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, "ln"),
        "attn": L.attention_init(ks[0], cfg, "attn"),
        "ln2": L.rmsnorm_init(cfg.d_model, "ln"),
        "mlp": L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, "mlp"),
    }


def layer_stacks(cfg: ModelConfig) -> List[Tuple[str, int, int]]:
    """(parameter key, first layer, end layer) of each stack of equal
    layers, in order: the leading dense layers (``dense_layers``), then
    the rest (``layers``)."""
    nd = cfg.first_dense_layers
    if not nd:
        return [("layers", 0, cfg.n_layers)]
    return [("dense_layers", 0, nd), ("layers", nd, cfg.n_layers)]


def init_params(cfg: ModelConfig, rng) -> PyTree:
    k_emb, k_layers, k_enc, k_f = jax.random.split(rng, 4)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embed": L.embed_init(k_emb, cfg.vocab, cfg.d_model),
        "final_ln": L.rmsnorm_init(cfg.d_model, "ln"),
    }
    for key, lo, hi in layer_stacks(cfg):
        dense = key == "dense_layers"
        params[key] = jax.vmap(lambda k, d=dense: _layer_init(cfg, k, d))(
            layer_keys[lo:hi]
        )
    if not cfg.tie_embed:
        params["head"] = L.embed_init(k_f, cfg.vocab, cfg.d_model, "head")
    if cfg.enc_layers:
        enc_keys = jax.random.split(k_enc, cfg.enc_layers)
        params["enc_layers"] = jax.vmap(lambda k: _enc_layer_init(cfg, k))(enc_keys)
        params["enc_final_ln"] = L.rmsnorm_init(cfg.d_model, "ln")
    return params


def param_specs(cfg: ModelConfig) -> PyTree:
    """Abstract parameter shapes (no allocation) — dry-run input."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# Per-layer metadata (window pattern)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig) -> np.ndarray:
    """(L,) int32; 0 = global attention, >0 = sliding window size.

    Static (numpy) metadata: init_cache sizes buffers from it, so it must
    stay concrete under jax.eval_shape; scan converts it on use.
    """
    Ln = cfg.n_layers
    if cfg.global_every and cfg.local_window:
        # every Nth layer global, the others sliding (gemma2 alternates
        # with N = 2, trinity runs 3:1 with N = 4)
        N = cfg.global_every
        w = [(0 if (i + 1) % N == 0 else cfg.local_window) for i in range(Ln)]
    elif cfg.hybrid and cfg.local_window:
        # hymba: global attention on first / middle / last layers
        glb = {0, Ln // 2, Ln - 1}
        w = [(0 if i in glb else cfg.local_window) for i in range(Ln)]
    elif cfg.local_window:
        w = [cfg.local_window] * Ln
    else:
        w = [0] * Ln
    return np.asarray(w, dtype=np.int32)


# Largest per-scan-step unroll we accept to keep windows static: a period-p
# pattern scans L/p steps of a p-layer body, so HLO stays O(p) in depth.
MAX_WINDOW_PERIOD = 4


def window_period(windows: np.ndarray, max_period: int = MAX_WINDOW_PERIOD):
    """Smallest period ``p <= max_period`` of the window pattern, or None.

    ``p`` divides the layer count and ``windows[i] == windows[i % p]`` for
    all i — uniform models give 1, gemma-2 local/global alternation 2.
    None means the pattern is aperiodic (hymba's {first, mid, last}
    globals) and the caller must fall back to tracing the window through
    the scan carry (which disables fused-attention dispatch: the hook
    only serves static windows).
    """
    Ln = len(windows)
    for p in range(1, min(max_period, Ln) + 1):
        if Ln % p == 0 and all(
            int(windows[i]) == int(windows[i % p]) for i in range(Ln)
        ):
            return p
    return None


def _stack_period(layers: PyTree, period: int) -> PyTree:
    """Reshape stacked (L, ...) params to (L/p, p, ...) for a periodic scan."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] // period, period) + a.shape[1:]),
        layers,
    )


# ---------------------------------------------------------------------------
# Forward (train / prefill, full sequence)
# ---------------------------------------------------------------------------


def _positions(cfg: ModelConfig, B: int, S: int, offset: int = 0):
    pos = offset + jnp.arange(S, dtype=jnp.int32)
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope:
        return jnp.stack([pos, pos, pos], axis=-1)  # text: t=h=w
    return pos


def _rope_q(cfg, q, pos):
    # q: (B, H, S, D); pos: (B, S) or (B, S, 3)
    if cfg.mrope:
        return L.apply_mrope(q, pos[:, None], theta=cfg.rope_theta)
    return L.apply_rope(q, pos[:, None], theta=cfg.rope_theta)


def _attn_full(cfg, p, x, pos, window, chunk=1024):
    B, S, _ = x.shape
    q, k, v = L.qkv_proj(p, x, cfg)
    hd_dims = (cfg.n_heads, cfg.n_kv_heads)
    q = shd.shard(q, "act_heads", hd_dims)
    k = shd.shard(k, "act_kv_heads", hd_dims)
    v = shd.shard(v, "act_kv_heads", hd_dims)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q, k = _rope_local(cfg, q, k, pos, window)
    out = L.chunked_attention(
        q, k, v,
        causal=True,
        window=window,
        softcap=cfg.attn_softcap,
        chunk=min(chunk, S),
    )
    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.head_dim)
    if cfg.attn_gate:
        out = out * jax.nn.sigmoid(L.dense_op(x, p["wg"]))
    return L.dense_op(out, p["wo"]), (k, v)


def _rope_local(cfg, q, k, pos, window):
    """Rotary on q and k; with ``nope_global`` only on sliding-window
    layers (a traced window selects per layer)."""
    traced = isinstance(window, jax.core.Tracer)
    if cfg.nope_global and not traced and not window:
        return q, k
    rq, rk = _rope_q(cfg, q, pos), _rope_q(cfg, k, pos)
    if cfg.nope_global and traced:
        return jnp.where(window > 0, rq, q), jnp.where(window > 0, rk, k)
    return rq, rk


def _layer_fwd(
    cfg: ModelConfig, p: Dict, x, pos, window, collect_cache=False, cross_fn=None
):
    """One decoder layer: mixer (attn and/or SSD) → [cross-attn] → FFN."""
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    h = shd.shard(h, "residual")
    mix = jnp.zeros_like(x)
    kv = None
    ssm_state = None
    xkv = None
    if not cfg.attn_free:
        a, kv = _attn_full(cfg, p["attn"], h, pos, window)
        if cfg.post_norms:
            a = L.rmsnorm(a, p["post_ln1"], cfg.norm_eps)
        mix = mix + a
    if cfg.ssm_state:
        if collect_cache:
            s, ssm_state = L.ssd_mixer_with_state(p["ssd"], h, cfg)
        else:
            s = L.ssd_mixer(p["ssd"], h, cfg)
        mix = mix + s
    x = x + mix
    if cross_fn is not None:  # whisper: cross-attn between self-attn and FFN
        hx = L.rmsnorm(x, p["ln_x"], cfg.norm_eps)
        xo, xkv = cross_fn(p["xattn"], hx)
        x = x + xo
    if cfg.d_ff:
        h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        f = jnp.zeros_like(x)
        if "moe" in p:
            f = f + L.moe(p["moe"], h2, cfg)
            if cfg.moe_dense_residual:
                f = f + L.mlp(p["mlp"], h2, cfg.act)
        else:
            f = L.mlp(p["mlp"], h2, cfg.act)
        if cfg.post_norms:
            f = L.rmsnorm(f, p["post_ln2"], cfg.norm_eps)
        x = x + f
    return shd.shard(x, "residual"), kv, ssm_state, xkv


def _encoder(cfg: ModelConfig, params, frames):
    """Whisper encoder over precomputed frame embeddings (B, F, D)."""
    x = frames.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    B, F, _ = x.shape
    pos = _positions(cfg, B, F)

    def step(carry, p):
        h = L.rmsnorm(carry, p["ln1"], cfg.norm_eps)
        q, k, v = L.qkv_proj(p["attn"], h, cfg)
        q = _rope_q(cfg, q, pos)
        k = _rope_q(cfg, k, pos)
        o = L.chunked_attention(q, k, v, causal=False, chunk=min(1024, F))
        o = o.transpose(0, 2, 1, 3).reshape(B, F, cfg.n_heads * cfg.head_dim)
        carry = carry + L.dense_op(o, p["attn"]["wo"])
        h2 = L.rmsnorm(carry, p["ln2"], cfg.norm_eps)
        carry = carry + L.mlp(p["mlp"], h2, cfg.act)
        return carry, None

    x, _ = jax.lax.scan(step, x, params["enc_layers"])
    return L.rmsnorm(x, params["enc_final_ln"], cfg.norm_eps)


def _cross_attn(cfg, p, x, enc_out):
    B, S, _ = x.shape
    F = enc_out.shape[1]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(
        B, S, cfg.n_heads, cfg.head_dim
    ).transpose(0, 2, 1, 3)
    k = jnp.einsum("bfd,dh->bfh", enc_out, p["wk"]).reshape(
        B, F, cfg.n_kv_heads, cfg.head_dim
    ).transpose(0, 2, 1, 3)
    v = jnp.einsum("bfd,dh->bfh", enc_out, p["wv"]).reshape(
        B, F, cfg.n_kv_heads, cfg.head_dim
    ).transpose(0, 2, 1, 3)
    o = L.chunked_attention(q, k, v, causal=False, chunk=min(1024, F))
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.head_dim)
    return L.dense_op(o, p["wo"]), (k, v)


def forward(
    cfg: ModelConfig,
    params: PyTree,
    tokens: Optional[jnp.ndarray] = None,
    embeds: Optional[jnp.ndarray] = None,
    frames: Optional[jnp.ndarray] = None,
    remat: bool = False,
) -> jnp.ndarray:
    """Full-sequence forward -> logits (B, S, V)."""
    if embeds is not None:
        x = embeds.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    else:
        x = L.embed(tokens, params["embed"]) * math.sqrt(cfg.d_model)
    x = shd.shard(x, "residual")
    B, S, _ = x.shape
    pos = _positions(cfg, B, S)
    windows = layer_windows(cfg)
    enc_out = None
    if cfg.enc_layers:
        assert frames is not None, "whisper needs encoder frames"
        enc_out = _encoder(cfg, params, frames)

    cross = (
        (lambda pa, hx: _cross_attn(cfg, pa, hx, enc_out))
        if cfg.enc_layers
        else None
    )
    for key, lo, hi in layer_stacks(cfg):
        x = _scan_layers(cfg, params[key], x, pos, windows[lo:hi], cross, remat)
    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    logits = L.unembed(x, params["embed" if cfg.tie_embed else "head"])
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return shd.shard(logits, "logits")


def _scan_layers(cfg, layers, x, pos, windows, cross, remat):
    """One stack of equal layers as one scan.

    Static-window scan: when the stack's window pattern is periodic the
    window reaches each layer as a Python int closed over the scan body
    (the attention dispatch hook needs a concrete value to serve the
    fused kernel); only aperiodic patterns trace it through the scan.
    """
    period = window_period(windows)
    if period is None:

        def step(carry, inp):
            p, w = inp
            x = carry
            x, _, _, _ = _layer_fwd(cfg, p, x, pos, w, cross_fn=cross)
            return x, None

        xs = (layers, windows)
    else:
        win_static = [int(windows[j]) or None for j in range(period)]

        def step(carry, lp):
            x = carry
            for j in range(period):
                pj = (
                    jax.tree_util.tree_map(lambda a, j=j: a[j], lp)
                    if period > 1
                    else lp
                )
                x, _, _, _ = _layer_fwd(
                    cfg, pj, x, pos, win_static[j], cross_fn=cross
                )
            return x, None

        xs = layers if period == 1 else _stack_period(layers, period)

    if remat:
        # save only layer boundaries; recompute internals in backward
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, _ = jax.lax.scan(step, x, xs)
    return x


def loss_fn(cfg: ModelConfig, params: PyTree, batch: Dict) -> jnp.ndarray:
    """Next-token cross entropy.  batch: {"tokens": (B, S+1)} or
    {"embeds": (B, S, D), "labels": (B, S)} (+ "frames" for whisper)."""
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        labels = batch["tokens"][:, 1:]
        logits = forward(
            cfg, params, tokens=inputs, frames=batch.get("frames"), remat=True
        )
    else:
        labels = batch["labels"]
        logits = forward(
            cfg, params, embeds=batch["embeds"], frames=batch.get("frames"),
            remat=True,
        )
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean()


# ---------------------------------------------------------------------------
# KV / state cache (serving)
# ---------------------------------------------------------------------------


def _refuse_cached_steps(cfg: ModelConfig) -> None:
    """The cache-carrying steps (prefill into a cache, decode, serve) scan
    one homogeneous layer stack and unembed through the tied table; a
    stack with leading dense layers or an output head of its own runs the
    full-sequence :func:`forward` only."""
    if cfg.first_dense_layers or not cfg.tie_embed:
        raise NotImplementedError(
            f"{cfg.name}: decode and serving are not implemented for a "
            "layer stack with leading dense layers or an untied output "
            "head; only the full-sequence forward runs"
        )


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> PyTree:
    """Decode cache.  Sliding-window layers only allocate the window (ring
    buffer) — this is what makes hymba's 512k decode bounded."""
    _refuse_cached_steps(cfg)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    cache: Dict[str, Any] = {"pos": jnp.zeros((), dtype=jnp.int32)}
    Ln = cfg.n_layers
    if not cfg.attn_free:
        windows = layer_windows(cfg)
        # per-layer cache length: window size if local else full context
        kv_len = int(max(np.where(windows > 0, np.minimum(windows, max_seq), max_seq)))
        cache["k"] = jnp.zeros(
            (Ln, batch, cfg.n_kv_heads, kv_len, cfg.head_dim), dtype=dt
        )
        cache["v"] = jnp.zeros_like(cache["k"])
    if cfg.ssm_state:
        cache["state"] = jnp.zeros(
            (Ln, batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
            dtype=jnp.float32,
        )
    if cfg.enc_layers:
        cache["xk"] = jnp.zeros(
            (Ln, batch, cfg.n_kv_heads, cfg.enc_frames, cfg.head_dim), dtype=dt
        )
        cache["xv"] = jnp.zeros_like(cache["xk"])
    return cache


def cache_max_len(cfg: ModelConfig, max_seq: int) -> int:
    windows = np.asarray(layer_windows(cfg))
    if cfg.attn_free:
        return 0
    return int(max(np.where(windows > 0, np.minimum(windows, max_seq), max_seq)))


def prefill(
    cfg: ModelConfig,
    params: PyTree,
    cache: PyTree,
    tokens: Optional[jnp.ndarray] = None,
    embeds: Optional[jnp.ndarray] = None,
    frames: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, PyTree]:
    """Process the prompt, fill the cache, return last-position logits."""
    _refuse_cached_steps(cfg)
    if embeds is not None:
        x = embeds.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    else:
        x = L.embed(tokens, params["embed"]) * math.sqrt(cfg.d_model)
    B, S, _ = x.shape
    pos = _positions(cfg, B, S)
    windows = layer_windows(cfg)
    enc_out = None
    if cfg.enc_layers:
        enc_out = _encoder(cfg, params, frames)

    kv_len = cache["k"].shape[3] if "k" in cache else 0
    if kv_len and kv_len < S:
        # ring-buffer handoff assumes slot p %% kv_len alignment
        assert S % kv_len == 0, (S, kv_len)

    cross = (
        (lambda pa, hx: _cross_attn(cfg, pa, hx, enc_out))
        if cfg.enc_layers
        else None
    )

    def _layer_outs(x, p, w):
        x, kv, ssm_state, xkv = _layer_fwd(
            cfg, p, x, pos, w, collect_cache=True, cross_fn=cross
        )
        outs = {}
        if kv is not None:
            k, v = kv  # (B, KVH, S, D)
            if kv_len and kv_len < S:
                k, v = k[:, :, -kv_len:], v[:, :, -kv_len:]
            elif kv_len and kv_len > S:
                padw = ((0, 0), (0, 0), (0, kv_len - S), (0, 0))
                k, v = jnp.pad(k, padw), jnp.pad(v, padw)
            outs["k"], outs["v"] = k, v
        if ssm_state is not None:
            outs["state"] = ssm_state
        if xkv is not None:
            outs["xk"], outs["xv"] = xkv
        return x, outs

    # same static-window scan as forward(); see the comment there
    period = window_period(windows)
    if period is None:

        def step(carry, inp):
            p, w = inp
            return _layer_outs(carry, p, w)

        xs = (params["layers"], windows)
    else:
        win_static = [int(windows[j]) or None for j in range(period)]

        def step(carry, lp):
            x = carry
            outs_list = []
            for j in range(period):
                pj = (
                    jax.tree_util.tree_map(lambda a, j=j: a[j], lp)
                    if period > 1
                    else lp
                )
                x, outs = _layer_outs(x, pj, win_static[j])
                outs_list.append(outs)
            if period == 1:
                return x, outs_list[0]
            stacked = {
                key: jnp.stack([o[key] for o in outs_list])
                for key in outs_list[0]
            }
            return x, stacked

        xs = (
            params["layers"]
            if period == 1
            else _stack_period(params["layers"], period)
        )

    x, collected = jax.lax.scan(step, x, xs)
    if period is not None and period > 1:
        # (L/p, p, ...) -> (L, ...): scan step t carried layers t*p..t*p+p-1
        collected = {
            key: v.reshape((v.shape[0] * period,) + v.shape[2:])
            for key, v in collected.items()
        }
    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    logits = L.unembed(x[:, -1:], params["embed"])
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    new_cache = dict(cache)
    for key in ("k", "v", "state", "xk", "xv"):
        if key in collected:
            new_cache[key] = collected[key]
    new_cache["pos"] = jnp.asarray(S, dtype=jnp.int32)
    return logits, new_cache


def page_view(pool: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Gather one layer's page pool through the page table.

    ``pool`` is ``(n_pages, KVH, page_size, D)``; ``table`` is ``(B, P)``
    physical page ids, where the sentinel value ``n_pages`` (one past the
    pool) marks unallocated entries — the gather clamps those to the last
    page, whose contents are never attended to because the per-slot
    length mask only exposes positions the slot actually wrote.  Returns
    a ``(B, KVH, P * page_size, D)`` contiguous-looking cache view, so
    downstream attention (and its tuned ``attention_decode`` dispatch
    key, static in ``T = P * page_size``) is identical to the slot-pool
    layout."""
    B, P = table.shape
    KVH, ps, D = pool.shape[1:]
    g = pool[table]  # (B, P, KVH, ps, D); OOB sentinel rows clamp
    return g.transpose(0, 2, 1, 3, 4).reshape(B, KVH, P * ps, D)


def serve_step(
    cfg: ModelConfig,
    params: PyTree,
    cache: PyTree,
    tokens: jnp.ndarray,
    valid: jnp.ndarray,
) -> Tuple[jnp.ndarray, PyTree]:
    """One serving tick: decode lanes and prefill chunks in one program.

    ``tokens`` is ``(B, C)`` — lane ``b`` contributes its next
    ``valid[b]`` tokens this tick (a decode lane has ``valid == 1`` with
    its sampled token in column 0; a prefilling lane carries up to ``C``
    prompt tokens; an idle lane has ``valid == 0`` and touches nothing).
    Returns logits ``(B, 1, V)`` taken at each lane's last valid position
    plus the updated cache, and advances ``cache["pos"]`` by ``valid``.

    The cache may be contiguous (``(L, B, KVH, kv_len, D)`` lanes, the
    ``KVArena`` layout) or paged (``(L, n_pages, KVH, page_size, D)``
    pools plus ``cache["page_table"]`` ``(B, P)``, the ``PagedKVArena``
    layout); writes and the attention view read through the page
    indirection in the latter.  Invalid chunk columns — and any write
    routed through a sentinel page-table entry, e.g. a released slot —
    scatter out of bounds and are dropped, so idle lanes can never
    corrupt pages owned by live requests.  Only pure-attention decoders
    are supported (SSD state and encoder cross-attention have no
    variable-width chunk step); ``ServeConfig.resolved_for`` routes other
    families back to ``decode_step``."""
    _refuse_cached_steps(cfg)
    if cfg.attn_free or cfg.ssm_state or cfg.enc_layers:
        raise NotImplementedError(
            "serve_step needs a pure-attention decoder; use decode_step"
        )
    x = L.embed(tokens, params["embed"]) * math.sqrt(cfg.d_model)
    B, C = tokens.shape
    pos_vec = jnp.asarray(cache["pos"], jnp.int32)  # (B,)
    valid = jnp.asarray(valid, jnp.int32)
    pos_mat = pos_vec[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    pos = jnp.stack([pos_mat] * 3, axis=-1) if cfg.mrope else pos_mat
    windows = layer_windows(cfg)
    paged = "page_table" in cache
    if paged:
        table = cache["page_table"]
        n_pages, _, ps, _ = cache["k"].shape[1:]
        kv_len = table.shape[1] * ps
    else:
        kv_len = cache["k"].shape[3]
    wp = pos_mat % kv_len  # (B, C) ring write positions
    cmask = jnp.arange(C, dtype=jnp.int32)[None, :] < valid[:, None]
    bidx = jnp.arange(B)
    length = jnp.minimum(pos_vec + 1, kv_len)

    scanned = {key: cache[key] for key in ("k", "v")}

    def layer_step(x, p, w_arg, sc):
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k1, v1 = L.qkv_proj(p["attn"], h, cfg)
        q = _rope_q(cfg, q, pos)
        k1 = _rope_q(cfg, k1, pos)
        kv = k1.transpose(0, 2, 1, 3)  # (B, C, KVH, D)
        vv = v1.transpose(0, 2, 1, 3)
        if paged:
            phys = jnp.take_along_axis(table, wp // ps, axis=1)  # (B, C)
            phys = jnp.where(cmask, phys, n_pages)
            K = sc["k"].at[phys, :, wp % ps].set(
                kv.astype(sc["k"].dtype), mode="drop"
            )
            V = sc["v"].at[phys, :, wp % ps].set(
                vv.astype(sc["v"].dtype), mode="drop"
            )
            k_view, v_view = page_view(K, table), page_view(V, table)
        else:
            wpos = jnp.where(cmask, wp, kv_len)  # OOB -> dropped
            K = sc["k"].at[bidx[:, None], :, wpos].set(
                kv.astype(sc["k"].dtype), mode="drop"
            )
            V = sc["v"].at[bidx[:, None], :, wpos].set(
                vv.astype(sc["v"].dtype), mode="drop"
            )
            k_view, v_view = K, V
        a = L.decode_attention(
            q, k_view, v_view, length=length,
            window=w_arg,
            softcap=cfg.attn_softcap,
        )
        a = a.transpose(0, 2, 1, 3).reshape(B, C, cfg.n_heads * cfg.head_dim)
        a = L.dense_op(a, p["attn"]["wo"])
        if cfg.post_norms:
            a = L.rmsnorm(a, p["post_ln1"], cfg.norm_eps)
        x = x + a
        if cfg.d_ff:
            h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
            if cfg.moe_experts:
                f = L.moe(p["moe"], h2, cfg)
                if cfg.moe_dense_residual:
                    f = f + L.mlp(p["mlp"], h2, cfg.act)
            else:
                f = L.mlp(p["mlp"], h2, cfg.act)
            if cfg.post_norms:
                f = L.rmsnorm(f, p["post_ln2"], cfg.norm_eps)
            x = x + f
        return x, {"k": K, "v": V}

    # same static-window scan as forward(); see the comment there
    period = window_period(windows)
    if period is None:

        def step(carry, inp):
            p, w, sc = inp
            return layer_step(carry, p, jnp.where(w > 0, w, 0), sc)

        x, new_scanned = jax.lax.scan(
            step, x, (params["layers"], windows, scanned)
        )
    else:
        win_static = [int(windows[j]) or None for j in range(period)]

        def step(carry, inp):
            lp, sc = inp
            x = carry
            if period == 1:
                return layer_step(x, lp, win_static[0], sc)
            outs = []
            for j in range(period):
                pj = jax.tree_util.tree_map(lambda a, j=j: a[j], lp)
                scj = {key: v[j] for key, v in sc.items()}
                x, new_scj = layer_step(x, pj, win_static[j], scj)
                outs.append(new_scj)
            stacked = {
                key: jnp.stack([o[key] for o in outs]) for key in outs[0]
            }
            return x, stacked

        if period == 1:
            xs = (params["layers"], scanned)
        else:
            xs = (
                _stack_period(params["layers"], period),
                {
                    key: v.reshape(
                        (v.shape[0] // period, period) + v.shape[1:]
                    )
                    for key, v in scanned.items()
                },
            )
        x, new_scanned = jax.lax.scan(step, x, xs)
        if period > 1:
            new_scanned = {
                key: v.reshape((v.shape[0] * period,) + v.shape[2:])
                for key, v in new_scanned.items()
            }

    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    # sample each lane at its last valid position; keeping the gather
    # before the unembed leaves the dense workload key at m = B, the same
    # program the tuned decode dispatch already serves
    idx = jnp.clip(valid - 1, 0, C - 1)
    xs_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)  # (B,1,D)
    logits = L.unembed(xs_last, params["embed"])
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    new_cache = dict(cache)
    new_cache.update(new_scanned)
    new_cache["pos"] = pos_vec + valid
    return logits, new_cache


def decode_step(
    cfg: ModelConfig, params: PyTree, cache: PyTree, tokens: jnp.ndarray
) -> Tuple[jnp.ndarray, PyTree]:
    """One decode step.  tokens: (B, 1) -> logits (B, 1, V), new cache.

    ``cache["pos"]`` is either a scalar — every lane at the same position,
    the legacy batch-engine layout — or a per-slot ``(B,)`` vector, the
    continuous-batching KV-arena layout where each slot advances
    independently (writes land at per-lane ring slots, attention masks to
    per-lane lengths).  Like forward()/prefill(), periodic per-layer
    window patterns close Python-int windows over the scan body so the
    decode attention dispatch hook sees static windows."""
    _refuse_cached_steps(cfg)
    x = L.embed(tokens, params["embed"]) * math.sqrt(cfg.d_model)
    B = x.shape[0]
    p_now = cache["pos"]
    per_slot = jnp.ndim(p_now) > 0
    pos_vec = (
        p_now if per_slot else jnp.broadcast_to(p_now, (B,))
    ).astype(jnp.int32)
    pos = pos_vec[:, None]  # (B, 1) rope positions
    if cfg.mrope:
        pos = jnp.stack([pos, pos, pos], axis=-1)  # text: t=h=w
    windows = layer_windows(cfg)
    kv_len = cache["k"].shape[3] if "k" in cache else 0

    scanned = {k: cache[k] for k in ("k", "v", "state", "xk", "xv") if k in cache}

    def layer_step(x, p, w_arg, sc):
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        mix = jnp.zeros_like(x)
        new_sc = dict(sc)
        if not cfg.attn_free:
            q, k1, v1 = L.qkv_proj(p["attn"], h, cfg)
            q = _rope_q(cfg, q, pos)
            k1 = _rope_q(cfg, k1, pos)
            if per_slot:
                # each arena slot writes at its own ring position
                slots = pos_vec % kv_len
                bidx = jnp.arange(B)
                K = sc["k"].at[bidx, :, slots].set(
                    k1[:, :, 0, :].astype(sc["k"].dtype)
                )
                V = sc["v"].at[bidx, :, slots].set(
                    v1[:, :, 0, :].astype(sc["v"].dtype)
                )
            else:
                slot = p_now % kv_len
                K = jax.lax.dynamic_update_slice(
                    sc["k"], k1.astype(sc["k"].dtype), (0, 0, slot, 0)
                )
                V = jax.lax.dynamic_update_slice(
                    sc["v"], v1.astype(sc["v"].dtype), (0, 0, slot, 0)
                )
            new_sc["k"], new_sc["v"] = K, V
            length = jnp.minimum(pos_vec + 1, kv_len)
            # per-layer window: when the uniform stacked cache is longer
            # than a local layer's window (global layers force max length),
            # mask the excess; ring wraparound approximates window by slot.
            a = L.decode_attention(
                q, K, V, length=length,
                window=w_arg,
                softcap=cfg.attn_softcap,
            )
            a = a.transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * cfg.head_dim)
            a = L.dense_op(a, p["attn"]["wo"])
            if cfg.post_norms:
                a = L.rmsnorm(a, p["post_ln1"], cfg.norm_eps)
            mix = mix + a
        if cfg.ssm_state:
            s, st = L.ssd_decode_step(p["ssd"], h, sc["state"], cfg)
            new_sc["state"] = st
            mix = mix + s
        x = x + mix
        if cfg.enc_layers:
            hx = L.rmsnorm(x, p["ln_x"], cfg.norm_eps)
            q = jnp.einsum("bsd,dh->bsh", hx, p["xattn"]["wq"]).reshape(
                B, 1, cfg.n_heads, cfg.head_dim
            ).transpose(0, 2, 1, 3)
            a = L.decode_attention(
                q, sc["xk"], sc["xv"], length=jnp.asarray(cfg.enc_frames)
            )
            a = a.transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * cfg.head_dim)
            x = x + L.dense_op(a, p["xattn"]["wo"])
        if cfg.d_ff:
            h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
            f = jnp.zeros_like(x)
            if cfg.moe_experts:
                f = f + L.moe(p["moe"], h2, cfg)
                if cfg.moe_dense_residual:
                    f = f + L.mlp(p["mlp"], h2, cfg.act)
            else:
                f = L.mlp(p["mlp"], h2, cfg.act)
            if cfg.post_norms:
                f = L.rmsnorm(f, p["post_ln2"], cfg.norm_eps)
            x = x + f
        return x, new_sc

    # same static-window scan as forward(); see the comment there
    period = window_period(windows)
    if period is None:

        def step(carry, inp):
            p, w, sc = inp
            return layer_step(carry, p, jnp.where(w > 0, w, 0), sc)

        x, new_scanned = jax.lax.scan(
            step, x, (params["layers"], windows, scanned)
        )
    else:
        win_static = [int(windows[j]) or None for j in range(period)]

        def step(carry, inp):
            lp, sc = inp
            x = carry
            if period == 1:
                return layer_step(x, lp, win_static[0], sc)
            outs = []
            for j in range(period):
                pj = jax.tree_util.tree_map(lambda a, j=j: a[j], lp)
                scj = {key: v[j] for key, v in sc.items()}
                x, new_scj = layer_step(x, pj, win_static[j], scj)
                outs.append(new_scj)
            stacked = {
                key: jnp.stack([o[key] for o in outs]) for key in outs[0]
            }
            return x, stacked

        if period == 1:
            xs = (params["layers"], scanned)
        else:
            xs = (
                _stack_period(params["layers"], period),
                {
                    key: v.reshape(
                        (v.shape[0] // period, period) + v.shape[1:]
                    )
                    for key, v in scanned.items()
                },
            )
        x, new_scanned = jax.lax.scan(step, x, xs)
        if period > 1:
            new_scanned = {
                key: v.reshape((v.shape[0] * period,) + v.shape[2:])
                for key, v in new_scanned.items()
            }

    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    logits = L.unembed(x, params["embed"])
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    new_cache = dict(cache)
    new_cache.update(new_scanned)
    new_cache["pos"] = p_now + 1
    return logits, new_cache
