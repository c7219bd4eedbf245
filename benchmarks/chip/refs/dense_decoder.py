"""Seeded weights, step counts and the plain float32 reference of a dense
decoder: the reference module of every configuration file that names
``"reference": "dense_decoder"`` (the interface is in ``refs/__init__.py``).

The weights are made here, on the device, in one jitted call from the
seed, in bf16 (the dtype the configurations serve in) and laid out as the
program's parameter tree expects them.  The reference is the plain
forward pass written from the architecture's equations: token embedding
times sqrt(d), then per layer RMSNorm, rotary (rotate-half) q/k, causal
grouped-query softmax attention and a SiLU-gated MLP, each on the
residual; a final RMSNorm and the tied unembedding.  It follows the
repository's model where that departs from the published one (each
departure is listed in the configuration's file): the sqrt(d) scale of
the embedding, RMSNorm for LayerNorm, rotary over the whole head and
tied embeddings.  It runs in float32
at the highest matmul precision, one layer at a time so that it fits
beside the weights, and imports nothing of the program.

``quant="int8"`` is the control: the same reference with every linear
layer computed from int8 operands (weights per output channel,
activations per token, symmetric), the precision one step below bf16.

The step counts are what the algorithm needs in bf16 (``work.py``'s
rule): every matmul of every layer, the unembedding, and causal
attention over the pairs its mask admits.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from harness import prng_key
from work import BF16, causal_pairs

HIGHEST = jax.lax.Precision.HIGHEST

# the configuration file's keys as ``small`` shrinks them for the CPU
# tests, and the program's config fields that must follow
SMALL = dict(
    num_hidden_layers=2, hidden_size=48, num_attention_heads=3,
    num_key_value_heads=1, intermediate_size=96, vocab_size=256,
)
SMALL_PROGRAM = dict(
    n_layers=2, d_model=48, n_heads=3, n_kv_heads=1, d_ff=96, vocab=256,
    head_dim=16,
)


@dataclass(frozen=True)
class Sizes:
    """Layers, width, query and kv heads, head size, MLP width, vocabulary
    held on this chip; norm epsilon and rotary base."""

    L: int
    D: int
    H: int
    KVH: int
    hd: int
    F: int
    V: int
    eps: float
    theta: float


def sizes(conf: Dict) -> Sizes:
    """The shape keys of a configuration file, under short names."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return Sizes(
        L=conf["num_hidden_layers"],
        D=d,
        H=h,
        KVH=conf["num_key_value_heads"],
        hd=conf.get("head_dim") or d // h,
        F=conf["intermediate_size"],
        V=conf["vocab_size"],
        eps=float(conf.get("rms_norm_eps", conf.get("layer_norm_eps"))),
        theta=float(conf["rope_theta"]),
    )


def program_fields(conf: Dict) -> Dict:
    """The program's ModelConfig fields this reference describes."""
    z = sizes(conf)
    return {
        "family": "dense", "n_layers": z.L, "d_model": z.D, "n_heads": z.H,
        "n_kv_heads": z.KVH, "head_dim": z.hd, "d_ff": z.F, "vocab": z.V,
        "norm_eps": z.eps, "rope_theta": z.theta,
    }


def small(conf: Dict) -> Dict:
    """The configuration file shrunk for the CPU: two layers of width 48."""
    out = dict(conf, **SMALL)
    out["overrides"] = dict(conf.get("overrides", {}), **SMALL_PROGRAM)
    return out


@partial(jax.jit, static_argnums=0)
def _init(z: Sizes, key) -> Dict:
    L, D, H, KVH, hd, F, V = z.L, z.D, z.H, z.KVH, z.hd, z.F, z.V
    ks = jax.random.split(key, 10)

    def normal(k, shape, fan_in):
        x = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        return x.astype(jnp.bfloat16)

    def norm_w(k, shape):
        return 1.0 + 0.1 * jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0)

    return {
        "embed": normal(ks[0], (V, D), D),
        "layers": {
            "ln1": norm_w(ks[1], (L, D)),
            "attn": {
                "wq": normal(ks[2], (L, D, H * hd), D),
                "wk": normal(ks[3], (L, D, KVH * hd), D),
                "wv": normal(ks[4], (L, D, KVH * hd), D),
                "wo": normal(ks[5], (L, H * hd, D), H * hd),
            },
            "ln2": norm_w(ks[6], (L, D)),
            "mlp": {
                "wi": normal(ks[7], (L, D, F), D),
                "wg": normal(ks[8], (L, D, F), D),
                "wo": normal(ks[9], (L, F, D), F),
            },
        },
        "final_ln": norm_w(jax.random.fold_in(key, 11), (D,)),
    }


def make_params(z: Sizes, seed: int) -> Dict:
    return _init(z, prng_key(seed))


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


@partial(jax.jit, static_argnums=(0, 4))
def _layer(z: Sizes, p, x, eps_theta, quant):
    eps, theta = eps_theta
    B, S, D = x.shape
    H, KVH, hd = z.H, z.KVH, z.hd
    h = _rms(x, p["ln1"], eps)

    def heads(t, n):
        return t.reshape(B, S, n, hd).transpose(0, 2, 1, 3)

    q = _rope(heads(_linear(h, p["attn"]["wq"], quant), H), theta)
    k = _rope(heads(_linear(h, p["attn"]["wk"], quant), KVH), theta)
    v = heads(_linear(h, p["attn"]["wv"], quant), KVH)
    qg = q.reshape(B, KVH, H // KVH, S, hd)
    s = jnp.einsum("bkgsd,bktd->bkgst", qg, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bkgst,bktd->bkgsd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    a = a.reshape(B, H, S, hd).transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    x = x + _linear(a, p["attn"]["wo"], quant)
    h = _rms(x, p["ln2"], eps)
    m = jax.nn.silu(_linear(h, p["mlp"]["wg"], quant)) * _linear(
        h, p["mlp"]["wi"], quant
    )
    return x + _linear(m, p["mlp"]["wo"], quant)


@partial(jax.jit, static_argnums=(3,))
def _head(x, final_ln, embed, quant_eps):
    quant, eps = quant_eps
    x = _rms(x, final_ln, eps)
    return _linear(x, embed.T, quant)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32) * math.sqrt(embed.shape[1])


def forward(z: Sizes, params: Dict, tokens, quant: Optional[str] = None):
    """Logits (B, S, V) in float32 for ``tokens`` (B, S)."""
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(z.L):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        x = _layer(z, p, x, (z.eps, z.theta), quant)
    return _head(x, params["final_ln"], params["embed"], (quant, z.eps))


# ---------------------------------------------------------------------------
# Step counts
# ---------------------------------------------------------------------------


def layer_params(z: Sizes) -> int:
    """Matmul parameters of one layer (norm weights are not matmuls)."""
    attn = z.D * z.H * z.hd * 2 + z.D * z.KVH * z.hd * 2
    return attn + 3 * z.D * z.F


def weight_bytes(z: Sizes) -> int:
    return (z.L * layer_params(z) + z.V * z.D) * BF16


def forward_flops(z: Sizes, batch: int, seq: int) -> int:
    """One causal forward over ``batch`` sequences of ``seq`` tokens,
    logits at every position."""
    tokens = batch * seq
    mm = 2 * (z.L * layer_params(z) + z.V * z.D) * tokens
    attn = z.L * 4 * batch * z.H * causal_pairs(seq, seq) * z.hd
    return mm + attn


def serve_tick(z: Sizes, lanes: Sequence[Tuple[int, int]]) -> Tuple[int, int]:
    """(flops, bytes) one serving tick needs for its real tokens.

    ``lanes`` holds ``(new_tokens, context)`` for each lane that carries
    work: ``new_tokens`` enter at the end of a ``context``-long sequence
    (their own positions included), and one row of logits is taken per
    lane.  Bytes: every weight once, plus the live KV cache each lane
    reads and the KV it writes."""
    flops, kv = 0, 0
    per_tok = 2 * z.L * layer_params(z)
    kv_tok = z.L * 2 * z.KVH * z.hd * BF16
    for n, ctx in lanes:
        flops += per_tok * n + 2 * z.V * z.D
        flops += z.L * 4 * z.H * causal_pairs(n, ctx) * z.hd
        kv += ctx * kv_tok
    return flops, weight_bytes(z) + kv
