"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel at smollm-135m widths
(d_model=576, d_ff=1536, vocab 49152, 9 query / 3 kv heads, head_dim 64)
and compiles it with the TPU compiler for a chip that is described, not
attached.  This is what the Pallas interpreter cannot check: Mosaic's
block-shape and layout rules.  The topology is described inside a fixture
only, so a test worker that is never given this file never loads the TPU
library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends.pallas_backend import MATMUL_ALIGNS, snap_blocks
from repro.kernels import flash_attention as fa
from repro.kernels import matmul as mm
from repro.kernels import softmax as sm

TOKENS = 128  # the extraction tile: batch 1 x seq 128
D, DFF, VOCAB = 576, 1536, 49152
H, KVH, HD = 9, 3, 64
SLOTS, KV_LEN, PAGE = 4, 256, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo  # the kernel is there, not a fallback


F32 = jnp.float32


@pytest.mark.parametrize(
    "k,n", [(D, DFF), (DFF, D), (D, VOCAB)], ids=["up", "down", "unembed"]
)
def test_dense_default_blocks(one_chip, k, n):
    blocks = snap_blocks((TOKENS, n, k), mm.DEFAULT_BLOCKS, MATMUL_ALIGNS)
    _compile(
        lambda x, w: mm.matmul(x, w, block_sizes=blocks, interpret=False),
        one_chip, ((TOKENS, k), F32), ((k, n), F32),
    )


def test_batch_matmul(one_chip):
    # attention scores as the extractor keys them: (B*KVH, G*S, D) x (D, S)
    blocks = snap_blocks((H // KVH * TOKENS, TOKENS, HD), mm.DEFAULT_BLOCKS,
                         MATMUL_ALIGNS)
    _compile(
        lambda a, b: mm.batch_matmul(a, b, block_sizes=blocks, interpret=False),
        one_chip,
        ((KVH, H // KVH * TOKENS, HD), F32), ((KVH, HD, TOKENS), F32),
    )


def test_prefill_flash_attention(one_chip):
    _compile(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
        one_chip,
        ((1, H, TOKENS, HD), F32), ((1, KVH, TOKENS, HD), F32),
        ((1, KVH, TOKENS, HD), F32),
    )


def test_decode_attention(one_chip):
    _compile(
        lambda q, k, v, b: fa.decode_flash_attention(q, k, v, b, interpret=False),
        one_chip,
        ((SLOTS, KVH, H // KVH, HD), F32), ((SLOTS, KVH, KV_LEN, HD), F32),
        ((SLOTS, KVH, KV_LEN, HD), F32), ((SLOTS, KV_LEN), F32),
    )


def test_paged_decode_attention(one_chip):
    pages = SLOTS * KV_LEN // PAGE
    _compile(
        lambda q, k, v, t, b: fa.paged_decode_flash_attention(
            q, k, v, t, b, interpret=False
        ),
        one_chip,
        ((SLOTS, KVH, H // KVH, HD), F32), ((pages, KVH, PAGE, HD), F32),
        ((pages, KVH, PAGE, HD), F32), ((SLOTS, KV_LEN // PAGE), jnp.int32),
        ((SLOTS, KV_LEN), F32),
    )


def test_row_softmax(one_chip):
    # attention probabilities of one prefill tile, rows = heads x queries
    _compile(
        lambda x: sm.row_softmax(x, block_rows=128, interpret=False),
        one_chip, ((H * TOKENS, TOKENS), F32),
    )
