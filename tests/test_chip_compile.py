"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel at smollm-135m widths
(d_model=576, d_ff=1536, vocab 49152, 9 query / 3 kv heads, head_dim 64),
the dense kernel at stablelm-3b widths or the grouped kernel at
trinity-mini's, and compiles it with the TPU compiler for a chip that is described, not
attached.  This is what the Pallas interpreter cannot check: Mosaic's
block-shape and layout rules.  Each kernel also carries its identity into
the compiled op, as the profiler's trace prints it: the op is named by its
template and its ``kernel_metadata`` holds the tuned record's workload key,
blocks and dtype.  (The ``ssd`` kernel is left out: Mosaic has no lowering
for its ``cumsum``.)  The topology is described inside a fixture
only, so a test worker that is never given this file never loads the TPU
library.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends.pallas_backend import MatmulShape, snap_blocks
from repro.kernels import flash_attention as fa
from repro.kernels import grouped_matmul as gm
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
    return hlo


def _identity(hlo):
    """(template, kernel_metadata) of the Pallas op in compiled HLO: the
    op is named ``<template>.<n>`` and its metadata is a JSON object in
    the ``kernel_metadata`` frontend attribute."""
    m = re.search(r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', hlo)
    at = hlo.index("kernel_metadata=", m.start()) + len("kernel_metadata=")
    meta, _ = json.JSONDecoder().raw_decode(hlo, at)
    return m.group(1).rsplit(".", 1)[0], meta


F32 = jnp.float32


@pytest.mark.parametrize(
    "k,n", [(D, DFF), (DFF, D), (D, VOCAB)], ids=["up", "down", "unembed"]
)
def test_dense_default_blocks(one_chip, k, n):
    blocks = snap_blocks((TOKENS, n, k), mm.DEFAULT_BLOCKS,
                         MatmulShape(1, TOKENS, n, k, 4).aligns)
    _compile(
        lambda x, w: mm.matmul(x, w, block_sizes=blocks, interpret=False),
        one_chip, ((TOKENS, k), F32), ((k, n), F32),
    )


# (m, k, n) of stablelm-3b's dense ops at 4 x 2048 tokens
STABLELM_DENSE = {
    "up": (8192, 2560, 6912), "qkvo": (8192, 2560, 2560),
    "down": (8192, 6912, 2560), "unembed": (8192, 2560, 50304),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("op", sorted(STABLELM_DENSE))
def test_dense_largest_legal_block(one_chip, op, dtype):
    # the block of the matmul space that takes the most VMEM compiles
    # within the kernel's default scoped VMEM
    m, k, n = STABLELM_DENSE[op]
    shape = MatmulShape(1, m, n, k, jnp.dtype(dtype).itemsize)
    blocks = max(shape.legal_blocks(), key=shape.vmem_bytes)
    _compile(
        lambda x, w: mm.matmul(x, w, block_sizes=blocks, interpret=False),
        one_chip, ((m, k), dtype), ((k, n), dtype),
    )


# (k, n) of trinity-mini's expert projections: 2 x 8192 tokens, 8 of 128
# experts each, so m = 131072 (token, slot) rows in 128 groups
TRINITY_GROUPED = {"up": (2048, 1024), "down": (1024, 2048)}


@pytest.mark.parametrize("op", sorted(TRINITY_GROUPED))
def test_grouped_largest_legal_block(one_chip, op):
    # the grouped block that takes the most VMEM compiles within the
    # kernel's default scoped VMEM, group bookkeeping prefetched
    k, n = TRINITY_GROUPED[op]
    shape = MatmulShape(1, 131072, n, k, 2, groups=128)
    blocks = max(shape.legal_blocks(), key=shape.vmem_bytes)
    _compile(
        lambda x, w, g: gm.grouped_matmul(x, w, g, block_sizes=blocks,
                                          interpret=False),
        one_chip, ((131072, k), jnp.bfloat16), ((128, k, n), jnp.bfloat16),
        ((128,), jnp.int32),
    )


def test_batch_matmul(one_chip):
    # attention scores as the extractor keys them: (B*KVH, G*S, D) x (D, S)
    shape = MatmulShape(KVH, H // KVH * TOKENS, TOKENS, HD, 4)
    blocks = snap_blocks(shape.dims, mm.DEFAULT_BLOCKS, shape.aligns)
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


BF16 = jnp.bfloat16
TASK = "task/of=the_record"

# template -> (kernel called with task=TASK, operand shapes, blocks, dtype)
IDENTITIES = {
    "dense": (
        lambda x, w: mm.matmul(x, w, block_sizes=(128, 128, 128),
                               interpret=False, task=TASK),
        [((TOKENS, 256), BF16), ((256, 256), BF16)], "128,128,128", "bfloat16",
    ),
    "batch_matmul": (
        lambda a, b: mm.batch_matmul(a, b, block_sizes=(128, 128, 64),
                                     interpret=False, task=TASK),
        [((KVH, TOKENS, HD), F32), ((KVH, HD, TOKENS), F32)],
        "128,128,64", "float32",
    ),
    "flash_attention": (
        lambda q, k, v: fa.flash_attention(q, k, v, block_q=64, block_kv=128,
                                           interpret=False, task=TASK),
        [((1, H, TOKENS, HD), F32), ((1, KVH, TOKENS, HD), F32),
         ((1, KVH, TOKENS, HD), F32)], "64,128", "float32",
    ),
    "flash_decode": (
        lambda q, k, v, b: fa.decode_flash_attention(
            q, k, v, b, block_kv=128, interpret=False, task=TASK),
        [((SLOTS, KVH, H // KVH, HD), F32), ((SLOTS, KVH, KV_LEN, HD), F32),
         ((SLOTS, KVH, KV_LEN, HD), F32), ((SLOTS, KV_LEN), F32)],
        "128", "float32",
    ),
    "paged_decode": (
        lambda q, k, v, t, b: fa.paged_decode_flash_attention(
            q, k, v, t, b, interpret=False, task=TASK),
        [((SLOTS, KVH, H // KVH, HD), F32),
         ((SLOTS * KV_LEN // PAGE, KVH, PAGE, HD), F32),
         ((SLOTS * KV_LEN // PAGE, KVH, PAGE, HD), F32),
         ((SLOTS, KV_LEN // PAGE), jnp.int32), ((SLOTS, KV_LEN), F32)],
        str(PAGE), "float32",
    ),
    "grouped_dense": (
        lambda x, w, g: gm.grouped_matmul(x, w, g, block_sizes=(128, 128, 256),
                                          interpret=False, task=TASK),
        [((1024, 256), BF16), ((8, 256, 128), BF16), ((8,), jnp.int32)],
        "128,128,256", "bfloat16",
    ),
    "row_softmax": (
        lambda x: sm.row_softmax(x, block_rows=64, interpret=False, task=TASK),
        [((H * TOKENS, TOKENS), F32)], "64", "float32",
    ),
}


@pytest.mark.parametrize("template", sorted(IDENTITIES))
def test_kernel_carries_its_identity(one_chip, template):
    fn, shapes, blocks, dtype = IDENTITIES[template]
    name, meta = _identity(_compile(fn, one_chip, *shapes))
    assert name == template
    assert meta == {"task": TASK, "blocks": blocks, "dtype": dtype}


def test_kernel_outside_a_record_has_no_task(one_chip):
    _, meta = _identity(_compile(
        lambda x: sm.row_softmax(x, interpret=False), one_chip,
        ((H * TOKENS, TOKENS), F32),
    ))
    assert meta["task"] == ""
