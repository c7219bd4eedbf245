"""Seeded weights, step counts and the plain float32 reference of an
``afmoe`` decoder (Arcee's Trinity family): the reference module of every
configuration file that names ``"reference": "afmoe"`` (the interface is in
``refs/__init__.py``).

The architecture, from the configuration file's published keys and the
layer equations listed under its ``assumed``:

* token embedding times sqrt(hidden_size);
* per layer, attention: RMSNorm, q/k/v projections, RMSNorm of q and k
  per head, rotary (rotate-half) on sliding-window layers only (the
  ``full_attention`` layers have no positional encoding), causal grouped-
  query softmax attention (a window of ``sliding_window`` keys on
  ``sliding_attention`` layers), the output times ``sigmoid(h W_gate)``,
  the output projection and an RMSNorm of the result before it joins the
  residual;
* then the feed-forward part, also between an RMSNorm and an RMSNorm of
  its result: a SiLU-gated MLP of ``intermediate_size`` on the leading
  ``num_dense_layers`` layers; on the others, ``num_experts`` routed
  SiLU-gated experts of ``moe_intermediate_size`` and
  ``num_shared_experts`` shared ones.  The router's scores are
  ``s = sigmoid(h W_r)``; the ``num_experts_per_tok`` experts of largest
  ``s + expert_bias`` are chosen (the bias steers the choice only) and
  weighted by their own scores over the sum of the chosen scores, times
  ``route_scale``;
* a final RMSNorm and the output head (not tied to the embedding).

The layers run are the first ``num_hidden_layers`` of ``layer_types``.
The weights are made on the device in one jitted call from the seed
(XLA's ``rbg`` bit generator, keyed by the seed), in bf16, laid out as
the program's parameter tree expects them (leading dense layers under
``dense_layers``, the rest under ``layers``), with a seeded non-zero
``expert_bias`` so that choosing and weighting differ.

The reference runs in float32 at the highest matmul precision, one layer
at a time so that it fits beside the weights, attention in blocks of
queries, and each routed expert on its own tokens only; it imports
nothing of the program.  ``quant="int8"`` is the control: every linear
layer (router and experts included) computed from int8 operands (weights
per output channel, activations per token, symmetric), the precision one
step below bf16.

The step counts are what the algorithm needs in bf16 (``work.py``'s
rule): every matmul of every layer with each token's routed experts only
(top-k expert rows a token), the router, the shared experts and the
head, and attention over the (query, key) pairs its mask admits.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import prng_key
from work import BF16, causal_pairs

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING = "sliding_attention"
Q_BLOCK = 1024  # query rows per attention block of the reference
ROW_BUCKET = 256  # an expert's token count is padded to a multiple of this

# the configuration file's keys as ``small`` shrinks them for the CPU
# tests (6 layers: the 2 dense ones and one period of 4), and the
# program's config fields that must follow
SMALL = dict(
    num_hidden_layers=6, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    vocab_size=256, sliding_window=8,
)
SMALL_PROGRAM = dict(
    n_layers=6, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
    moe_d_ff=32, moe_experts=8, moe_top_k=2, vocab=256, local_window=8,
)


@dataclass(frozen=True)
class Sizes:
    """Layers run and leading dense layers; width, query and kv heads,
    head size, dense and expert MLP widths; experts, experts a token,
    shared experts; vocabulary held on this chip; each layer's window
    (0: full attention); norm epsilon, rotary base, route scale."""

    L: int
    Ld: int
    D: int
    H: int
    KVH: int
    hd: int
    F: int
    Fe: int
    E: int
    K: int
    Es: int
    V: int
    windows: Tuple[int, ...]
    eps: float
    theta: float
    scale: float


def sizes(conf: Dict) -> Sizes:
    """The shape keys of a configuration file, under short names."""
    L = conf["num_hidden_layers"]
    kinds = conf["layer_types"][:L]
    return Sizes(
        L=L,
        Ld=conf["num_dense_layers"],
        D=conf["hidden_size"],
        H=conf["num_attention_heads"],
        KVH=conf["num_key_value_heads"],
        hd=conf["head_dim"],
        F=conf["intermediate_size"],
        Fe=conf["moe_intermediate_size"],
        E=conf["num_experts"],
        K=conf["num_experts_per_tok"],
        Es=conf["num_shared_experts"],
        V=conf["vocab_size"],
        windows=tuple(conf["sliding_window"] if k == SLIDING else 0
                      for k in kinds),
        eps=float(conf["rms_norm_eps"]),
        theta=float(conf["rope_theta"]),
        scale=float(conf["route_scale"]),
    )


def program_fields(conf: Dict) -> Dict:
    """The program's ModelConfig fields this reference describes."""
    z = sizes(conf)
    every = conf["global_attn_every_n_layers"]
    if any((w == 0) != ((i + 1) % every == 0) for i, w in enumerate(z.windows)):
        raise ValueError(
            f"{conf['name']}: layer_types is not one full layer in "
            f"{every}, the pattern the program runs"
        )
    if conf["score_func"] != "sigmoid" or not conf["route_norm"]:
        raise ValueError(f"{conf['name']}: the program routes by sigmoid "
                         "scores normalised over the chosen experts")
    return {
        "family": "moe", "n_layers": z.L, "d_model": z.D, "n_heads": z.H,
        "n_kv_heads": z.KVH, "head_dim": z.hd, "d_ff": z.F, "vocab": z.V,
        "norm_eps": z.eps, "rope_theta": z.theta,
        "first_dense_layers": z.Ld, "moe_experts": z.E, "moe_top_k": z.K,
        "moe_d_ff": z.Fe, "moe_shared_experts": z.Es,
        "moe_router": "sigmoid", "moe_route_scale": z.scale,
        "moe_capacity_factor": -1.0,
        "local_window": conf["sliding_window"], "global_every": every,
        "nope_global": True, "qk_norm": True, "attn_gate": True,
        "post_norms": True, "tie_embed": bool(conf["tie_word_embeddings"]),
    }


def small(conf: Dict) -> Dict:
    """The configuration file shrunk for the CPU: six layers of width 64,
    8 experts, 2 a token, a window of 8."""
    out = dict(conf, **SMALL)
    out["overrides"] = dict(conf.get("overrides", {}), **SMALL_PROGRAM)
    return out


# ---------------------------------------------------------------------------
# Seeded weights
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=0)
def _init(z: Sizes, key) -> Dict:
    D, H, KVH, hd, V = z.D, z.H, z.KVH, z.hd, z.V
    keys = iter(jax.random.split(key, 64))

    def normal(shape, fan_in):
        x = jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)
        return x.astype(jnp.bfloat16)

    def norm_w(shape):
        return 1.0 + 0.1 * jax.random.uniform(
            next(keys), shape, jnp.float32, -1.0, 1.0)

    def stack(n, dense):
        p = {
            "ln1": norm_w((n, D)),
            "post_ln1": norm_w((n, D)),
            "ln2": norm_w((n, D)),
            "post_ln2": norm_w((n, D)),
            "attn": {
                "wq": normal((n, D, H * hd), D),
                "wk": normal((n, D, KVH * hd), D),
                "wv": normal((n, D, KVH * hd), D),
                "wo": normal((n, H * hd, D), H * hd),
                "wg": normal((n, D, H * hd), D),
                "q_norm": norm_w((n, hd)),
                "k_norm": norm_w((n, hd)),
            },
        }
        if dense:
            p["mlp"] = {"wi": normal((n, D, z.F), D),
                        "wg": normal((n, D, z.F), D),
                        "wo": normal((n, z.F, D), z.F)}
            return p
        Fs = z.Fe * z.Es
        p["moe"] = {
            "router": normal((n, D, z.E), D).astype(jnp.float32),
            "expert_bias": 0.1 * jax.random.uniform(
                next(keys), (n, z.E), jnp.float32, -1.0, 1.0),
            "wi": normal((n, z.E, D, z.Fe), D),
            "wg": normal((n, z.E, D, z.Fe), D),
            "wo": normal((n, z.E, z.Fe, D), z.Fe),
            "shared": {"wi": normal((n, D, Fs), D),
                       "wg": normal((n, D, Fs), D),
                       "wo": normal((n, Fs, D), Fs)},
        }
        return p

    return {
        "embed": normal((V, D), D),
        "head": normal((V, D), D),
        "final_ln": norm_w((D,)),
        "dense_layers": stack(z.Ld, True),
        "layers": stack(z.L - z.Ld, False),
    }


def make_params(z: Sizes, seed: int) -> Dict:
    # XLA's own bit generator ("rbg"), keyed from the seed's threefry key:
    # for a described v5e the init compiles in 12.5 s against threefry's
    # 31.8 s, most of a cold run's weight time
    k = jnp.asarray(prng_key(seed), jnp.uint32)
    key = jax.random.wrap_key_data(
        jnp.concatenate([k, jax.random.fold_in(k, 1)]), impl="rbg"
    )
    return _init(z, key)


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------


def _q8(x, axis):
    """Symmetric int8 rounding of ``x`` with one scale per slice along
    ``axis`` (the contraction axis), returned dequantized in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _linear(x, w, quant):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x: (B, heads, S, hd); rotate-half over the whole head
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _glu(h, p, quant):
    return _linear(
        jax.nn.silu(_linear(h, p["wg"], quant)) * _linear(h, p["wi"], quant),
        p["wo"], quant,
    )


def _at(stack, j):
    """Layer ``j`` of a stack of layers' weights (inside a jitted call,
    so that one program serves every layer of the stack)."""
    return jax.tree.map(lambda a: a[j], stack)


@partial(jax.jit, static_argnums=(0, 4, 5))
def _attention(z: Sizes, stack, j, x, window: int, quant):
    """The attention half of layer ``j`` of ``stack``, residual included."""
    p = _at(stack, j)
    B, S, D = x.shape
    H, KVH, hd = z.H, z.KVH, z.hd
    h = _rms(x, p["ln1"], z.eps)

    def heads(t, n, norm):
        t = t.reshape(B, S, n, hd).transpose(0, 2, 1, 3)
        return _rms(t, norm, z.eps)

    q = heads(_linear(h, p["attn"]["wq"], quant), H, p["attn"]["q_norm"])
    k = heads(_linear(h, p["attn"]["wk"], quant), KVH, p["attn"]["k_norm"])
    v = _linear(h, p["attn"]["wv"], quant).reshape(B, S, KVH, hd)
    v = v.transpose(0, 2, 1, 3)
    if window:
        q, k = _rope(q, z.theta), _rope(k, z.theta)
    qb = min(Q_BLOCK, S)
    qg = q.reshape(B, KVH, H // KVH, S // qb, qb, hd).transpose(3, 0, 1, 2, 4, 5)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        qpos = i * qb + jnp.arange(qb)
        s = jnp.einsum("bkgqd,bktd->bkgqt", qi, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        back = qpos[:, None] - kpos[None, :]
        keep = (back >= 0) & ((back < window) if window else True)
        s = jnp.where(keep, s, -jnp.inf)
        return jnp.einsum("bkgqt,bktd->bkgqd", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    a = jax.lax.map(block, (jnp.arange(S // qb), qg))  # (nq, B, KVH, G, qb, hd)
    a = a.transpose(1, 2, 3, 0, 4, 5).reshape(B, H, S, hd)
    a = a.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    a = a * jax.nn.sigmoid(_linear(h, p["attn"]["wg"], quant))
    return x + _rms(_linear(a, p["attn"]["wo"], quant), p["post_ln1"], z.eps)


@partial(jax.jit, static_argnums=(0, 4))
def _dense_mlp(z: Sizes, stack, j, x, quant):
    p = _at(stack, j)
    h = _rms(x, p["ln2"], z.eps)
    return x + _rms(_glu(h, p["mlp"], quant), p["post_ln2"], z.eps)


@partial(jax.jit, static_argnums=(0, 4))
def _route(z: Sizes, stack, j, x, quant):
    """(normed input (T, D), chosen experts (T, K), their weights (T, K),
    shared experts' output (T, D)) of layer ``j`` of ``stack``."""
    p = _at(stack, j)
    h = _rms(x, p["ln2"], z.eps).reshape(-1, z.D)
    s = jax.nn.sigmoid(_linear(h, p["moe"]["router"], quant))
    _, chosen = jax.lax.top_k(s + p["moe"]["expert_bias"], z.K)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * z.scale
    return h, chosen, w, _glu(h, p["moe"]["shared"], quant)


@partial(jax.jit, static_argnums=(7,))
def _expert(out, h, rows, weight, experts, j, e, quant):
    """``out`` plus expert ``e`` of layer ``j``'s SiLU-gated MLP of its
    tokens' rows ``rows`` (padding rows carry weight 0), each times its
    weight."""
    y = _glu(h[rows], {k: experts[k][j, e] for k in ("wi", "wg", "wo")}, quant)
    return out.at[rows].add(y * weight[:, None])


@partial(jax.jit, static_argnums=(0,))
def _moe_out(z: Sizes, stack, j, x, routed, shared):
    f = (routed + shared).reshape(x.shape)
    return x + _rms(f, stack["post_ln2"][j], z.eps)


def _moe(z: Sizes, stack, j, x, quant):
    """The expert half of a layer, each routed expert on its own tokens;
    also returns the experts each token was routed to (T, K).  An
    expert's rows are padded to a multiple of ``ROW_BUCKET``, so that a
    few shapes compile."""
    h, chosen, w, shared = _route(z, stack, j, x, quant)
    chosen, w = np.asarray(chosen), np.asarray(w)
    routed = jnp.zeros_like(h)
    for e in range(z.E):
        tok, slot = np.nonzero(chosen == e)
        if not len(tok):
            continue
        n = -(-len(tok) // ROW_BUCKET) * ROW_BUCKET
        rows = np.zeros(n, np.int32)
        rows[: len(tok)] = tok
        weight = np.zeros(n, np.float32)
        weight[: len(tok)] = w[tok, slot]
        routed = _expert(routed, h, rows, weight, stack["moe"], j,
                         np.int32(e), quant)
    return _moe_out(z, stack, j, x, routed, shared), chosen


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32) * math.sqrt(embed.shape[1])


@partial(jax.jit, static_argnums=(3,))
def _head(x, final_ln, head, quant_eps):
    quant, eps = quant_eps
    return _linear(_rms(x, final_ln, eps), head.T, quant)


def layer(params: Dict, i: int, z: Sizes) -> Tuple[Dict, np.int32, bool]:
    """The stack of layer ``i``'s weights, its index in the stack, and
    whether it is one of the dense layers."""
    dense = i < z.Ld
    stack = params["dense_layers"] if dense else params["layers"]
    return stack, np.int32(i if dense else i - z.Ld), dense


def forward(z: Sizes, params: Dict, tokens, quant: Optional[str] = None):
    """Logits (B, S, V) in float32 for ``tokens`` (B, S)."""
    return forward_routes(z, params, tokens, quant)[0]


def forward_routes(z: Sizes, params: Dict, tokens, quant: Optional[str] = None):
    """The logits, and the experts each token was routed to in each
    expert layer: a list of (B * S, K) arrays, layer by layer."""
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    chosen = []
    for i in range(z.L):
        stack, j, dense = layer(params, i, z)
        x = _attention(z, stack, j, x, z.windows[i], quant)
        if dense:
            x = _dense_mlp(z, stack, j, x, quant)
        else:
            x, c = _moe(z, stack, j, x, quant)
            chosen.append(c)
    return _head(x, params["final_ln"], params["head"], (quant, z.eps)), chosen


# ---------------------------------------------------------------------------
# Step counts
# ---------------------------------------------------------------------------


def window_pairs(s: int, w: int) -> int:
    """(query, key) pairs of a causal ``s``-token square whose queries see
    at most the ``w`` latest keys (themselves included); 0: no window."""
    if not w or w >= s:
        return causal_pairs(s, s)
    return causal_pairs(s, s) - causal_pairs(s - w, s - w)


def attn_params(z: Sizes) -> int:
    """Matmul parameters of one layer's attention: q, k, v, o and gate."""
    return 3 * z.D * z.H * z.hd + 2 * z.D * z.KVH * z.hd


def token_params(z: Sizes) -> int:
    """Matmul parameters one token goes through in a forward: every
    attention projection, the dense MLPs, its routed and the shared
    experts and the router of every expert layer, and the head."""
    moe = (z.K + z.Es) * 3 * z.D * z.Fe + z.D * z.E
    return (z.L * attn_params(z) + z.Ld * 3 * z.D * z.F
            + (z.L - z.Ld) * moe + z.V * z.D)


def weight_bytes(z: Sizes) -> int:
    moe = (z.E + z.Es) * 3 * z.D * z.Fe + z.D * z.E
    n = (z.L * attn_params(z) + z.Ld * 3 * z.D * z.F + (z.L - z.Ld) * moe
         + 2 * z.V * z.D)
    return n * BF16


def forward_flops(z: Sizes, batch: int, seq: int) -> int:
    """One causal forward over ``batch`` sequences of ``seq`` tokens,
    logits at every position."""
    mm = 2 * token_params(z) * batch * seq
    attn = sum(4 * batch * z.H * window_pairs(seq, w) * z.hd for w in z.windows)
    return mm + attn


def serve_tick(z: Sizes, lanes: Sequence[Tuple[int, int]]) -> Tuple[int, int]:
    """(flops, bytes) one serving tick needs for its real tokens.

    ``lanes`` holds ``(new_tokens, context)`` for each lane that carries
    work: ``new_tokens`` enter at the end of a ``context``-long sequence
    (their own positions included), and one row of logits is taken per
    lane.  Bytes: every weight once, plus the live KV cache each lane
    reads (a sliding layer's last ``window`` positions only)."""
    flops, kv = 0, 0
    per_tok = 2 * (token_params(z) - z.V * z.D)
    for n, ctx in lanes:
        flops += per_tok * n + 2 * z.V * z.D
        for w in z.windows:
            pairs = window_pairs(ctx, w) - window_pairs(ctx - n, w)
            flops += 4 * z.H * pairs * z.hd
            kv += min(ctx, w or ctx) * 2 * z.KVH * z.hd * BF16
    return flops, weight_bytes(z) + kv
