"""Seeded weights and the plain float32 reference of a dense decoder.

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
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def prng_key(seed: int):
    """A key from any whole number (the low and high 32 bits both count)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def norm_rope(conf: Dict) -> Tuple[float, float]:
    """(norm epsilon, rotary base) of a configuration file."""
    eps = conf.get("rms_norm_eps", conf.get("layer_norm_eps"))
    return float(eps), float(conf["rope_theta"])


def _shape_key(z: Dict[str, int]) -> Tuple:
    return tuple(sorted(z.items()))


@partial(jax.jit, static_argnums=0)
def _init(zk: Tuple, key) -> Dict:
    z = dict(zk)
    L, D, H, KVH, hd, F, V = (z[k] for k in ("L", "D", "H", "KVH", "hd", "F", "V"))
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


def make_params(z: Dict[str, int], seed: int) -> Dict:
    return _init(_shape_key(z), prng_key(seed))


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
def _layer(zk, p, x, eps_theta, quant):
    z = dict(zk)
    eps, theta = eps_theta
    B, S, D = x.shape
    H, KVH, hd = z["H"], z["KVH"], z["hd"]
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


def forward(
    z: Dict[str, int], params: Dict, tokens, eps: float, theta: float,
    quant: Optional[str] = None,
):
    """Logits (B, S, V) in float32 for ``tokens`` (B, S)."""
    zk = _shape_key(z)
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(z["L"]):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        x = _layer(zk, p, x, (float(eps), float(theta)), quant)
    return _head(x, params["final_ln"], params["embed"], (quant, float(eps)))
