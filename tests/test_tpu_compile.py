"""Compile the Pallas kernels for a described TPU v5e chip, at the widths of
the models they serve.

The TPU compiler is installed without a chip: it refuses here what the chip
would refuse (a block not aligned to the (8, 128) tiling, more fast memory
than a kernel may use), and each test also checks that the kernel reached
the program as a ``tpu_custom_call`` rather than interpret-mode HLO.
Nothing runs, so these tests say nothing about results or times; the
interpret-mode tests in ``test_kernels.py`` and ``test_quantized.py`` check
the values.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a test worker that decides
at import whether these tests exist would collect different tests from its
siblings.  Keep every such compile in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_matmul_compiles_at_qwen3_mlp_width(one_chip):
    """qwen3-0.6b's 1024 x 3072 up projection over 256 tokens."""
    _compile(lambda a, b: ops.matmul(a, b, interpret=False),
             _spec(one_chip, (256, 1024)), _spec(one_chip, (1024, 3072)))


@pytest.mark.parametrize("group", [32, 64, 128])
def test_q4_matmul_compiles_at_qwen3_width(one_chip, group):
    """The packed-int4 matmul at every group the quantizer is used with,
    32 (the default) included: a k block pinned to ``group`` K rows gave
    blocks narrower than the 128-lane tile, which the TPU refuses."""
    K, N = 1024, 3072
    _compile(lambda a, p, s: ops.q4_matmul(a, p, s, group=group,
                                           interpret=False),
             _spec(one_chip, (256, K)), _spec(one_chip, (K // 2, N), jnp.uint8),
             _spec(one_chip, (K // group, N)))


@pytest.mark.parametrize("seq", [2048, 32768])
def test_flash_attention_compiles_at_qwen3_heads(one_chip, seq):
    """head_dim 128, GQA 16/8, causal, up to qwen3's 32k context: whole-
    length K/V blocks ran out of fast memory from 8k tokens on."""
    q = _spec(one_chip, (1, 16, seq, 128))
    kv = _spec(one_chip, (1, 8, seq, 128))
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
             q, kv, kv)


def test_flash_attention_compiles_sliding_window(one_chip):
    """recurrentgemma-9b's local attention: head_dim 256, one KV head,
    window 2048 over 8k tokens."""
    q = _spec(one_chip, (1, 16, 8192, 256))
    kv = _spec(one_chip, (1, 1, 8192, 256))
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, window=2048,
                                                 interpret=False),
             q, kv, kv)


def test_lru_scan_compiles_at_recurrentgemma_width(one_chip):
    """recurrentgemma-9b's RG-LRU: 4096 channels over 2048 steps; a dynamic
    row of a loaded block has no TPU lowering, a row of a ref has."""
    a = _spec(one_chip, (1, 2048, 4096))
    _compile(lambda a, x: ops.lru_scan(a, x, interpret=False), a, a)
