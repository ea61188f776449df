"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e.

Interpret mode runs a kernel's body as ordinary JAX ops, so it never
sees what the chip's compiler refuses: a slice not aligned to the
tiling, more scoped VMEM than a kernel may use, a grid the Mosaic
lowering cannot express.  These tests compile each kernel natively
(``interpret=False``) for a described, unattached v5e chip at the widths
the serving path runs (qwen3-8b: 32 query and 8 KV heads of 128; the
decode kernel also at qwen2-7b's 28 and 4 heads and at a four-way
tensor shard's 8 and 2) and check that the compiled program holds the
kernel (``tpu_custom_call``).
Nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process at a time may load the TPU's
library, and every worker of a parallel test run imports this file.
The persistent compilation cache is off around the compiles, because a
program compiled for a described chip cannot be read back without one.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv2d import conv2d_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.paged_attention import (
    paged_attention_pallas, paged_prefill_attention_pallas)

# qwen3-8b attention widths; 8 sequences of 256 pages of 16 tokens
B, HQ, HKV, D, BS, NB = 8, 32, 8, 128, 16, 256
POOL_PAGES = B * NB + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool(sharding):
    return jax.ShapeDtypeStruct((POOL_PAGES, HKV, BS, D), jnp.bfloat16,
                                sharding=sharding)


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


# decode widths of the benchmark's cells and of a tensor shard: (slots,
# pages per slot, query heads, KV heads)
DECODE_WIDTHS = {
    "qwen3-8b": (24, 192, 32, 8),
    "qwen2-7b": (16, 384, 28, 4),
    "qwen3-8b-tp4-shard": (24, 192, 8, 2),
}


@pytest.mark.parametrize("widths", list(DECODE_WIDTHS.values()),
                         ids=list(DECODE_WIDTHS))
@pytest.mark.parametrize("read_dtype", [None, jnp.bfloat16],
                         ids=["f32_probs", "bf16_probs"])
def test_paged_decode_attention(one_chip, read_dtype, widths):
    b, nb, hq, hkv = widths
    q = jax.ShapeDtypeStruct((b, hq, 1, D), jnp.bfloat16, sharding=one_chip)
    pool = jax.ShapeDtypeStruct((b * nb + 1, hkv, BS, D), jnp.bfloat16,
                                sharding=one_chip)
    fn = functools.partial(paged_attention_pallas, interpret=False,
                           read_dtype=read_dtype)
    text = _compiled_text(fn, q, pool, pool, _i32(one_chip, b, nb),
                          _i32(one_chip, b))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("chunk", [128, 512])
def test_paged_prefill_attention(one_chip, chunk):
    q = jax.ShapeDtypeStruct((1, HQ, chunk, D), jnp.bfloat16,
                             sharding=one_chip)
    fn = functools.partial(paged_prefill_attention_pallas, interpret=False)
    text = _compiled_text(fn, q, _pool(one_chip), _pool(one_chip),
                          _i32(one_chip, 1, NB), _i32(one_chip, 1))
    assert "tpu_custom_call" in text


def test_flash_attention(one_chip):
    S = 2048
    q = jax.ShapeDtypeStruct((1, HQ, S, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, HKV, S, D), jnp.bfloat16, sharding=one_chip)
    fn = functools.partial(flash_attention_pallas, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)


def test_matmul(one_chip):
    a = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=one_chip)
    fn = functools.partial(matmul_pallas, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, a, a)


def test_conv2d_fig3_frame(one_chip):
    """The paper's fig3 frames are 384x384 f32 under a 3x3 Laplacian;
    ``ops.conv2d`` pads the rows so the output is a multiple of bh=8."""
    x = jax.ShapeDtypeStruct((384 + 2, 384), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3), jnp.float32, sharding=one_chip)
    fn = functools.partial(conv2d_pallas, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, x, w)
