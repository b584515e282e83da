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

import importlib.util
import json
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import repro.models.attention as attention
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_lse

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
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
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, kernels: int = 1):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') >= kernels
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


@pytest.mark.parametrize("heads", [(36, 4), (32, 8)])
def test_fused_attention_fwd_bwd_compile_at_cell_heads(one_chip, heads):
    """The forward, dq and dk/dv kernels at both benchmark cells' attention
    (starcoder2-7b 36/4 heads, mistral-nemo 32/8; 4096 tokens, head_dim
    128, batch 1), with the blocks and product dtypes ``models/attention``
    gives them on a TPU."""
    H, KV = heads
    T, hd = 4096, 128
    bq, bkv = attention._kernel_blocks(T, T)
    mxu, pv = attention._kernel_dtypes(jnp.float32, False)

    def loss(q, k, v):
        o = flash_attention_lse(q, k, v, block_q=bq, block_kv=bkv, mxu=mxu,
                                pv=pv, interpret=False)[0]
        return jnp.sum(o)

    kv = _spec(one_chip, (1, T, KV, hd))
    _compile(jax.grad(loss, argnums=(0, 1, 2)), _spec(one_chip, (1, T, H, hd)),
             kv, kv, kernels=3)


def _cell_step(topo, config_name: str, batch: int, seq: int = 4096):
    """A benchmark cell's train step (its configuration file, mapped to a
    ``ModelConfig`` as the benchmark's training cells map it, for
    sequences of ``seq`` tokens) over the configuration's mesh of described
    chips, and its state and batch as shapes."""
    from repro.core.topology import MeshTopology
    from repro.runtime.steps import make_train_step
    from repro.substrate.compat import make_mesh

    spec = importlib.util.spec_from_file_location(
        "tpu_compile_train_cells", ROOT / "benchmark/drivers/train.py")
    cells = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = cells
    spec.loader.exec_module(cells)
    config = json.loads(
        (ROOT / "benchmark/configs" / f"{config_name}.json").read_text())
    axes = config["mesh"]
    mesh = make_mesh(tuple(axes.values()), tuple(axes),
                     devices=topo.devices[:cells.n_chips(config)])
    opt = config["optimizer"]
    bundle = make_train_step(
        cells.model_config(config, seq),
        MeshTopology(dict(axes), slow_axes=("pod",) if "pod" in axes
                     else ()), mesh,
        mode=config["mode"], lr=opt["lr"], weight_decay=opt["weight_decay"],
        clip=opt["clip"], compute_dtype=jnp.float32)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             bundle.state_specs,
                             is_leaf=lambda x: isinstance(x, P))
    params = jax.eval_shape(bundle.model.init_params)
    state = {"params": params, "m": params, "v": params,
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, shardings)
    tokens = jax.ShapeDtypeStruct(
        (batch, seq + 1), jnp.int32,
        sharding=NamedSharding(mesh, bundle.batch_spec["tokens"]))
    return bundle.fn, state, {"tokens": tokens}


def _sc2_step(topo):
    """sc2-train-4k-1chip's train step over one described chip."""
    return _cell_step(topo, "starcoder2-7b-share1", batch=1)


def test_sc2_train_step_takes_the_fused_attention(topo, monkeypatch):
    """One train step of the sc2 cell compiled for a v5e chip through the
    fused kernels (forward, its remat, dq, dk/dv) needs less memory for
    its temporaries than through the KV-block scan, whose autodiff keeps
    every KV block's fp32 probabilities.  The program's backend check sees
    this CPU, so the test steers it to the TPU branch."""
    def compile_step():
        step, state, batch = _sc2_step(topo)
        return jax.jit(step, donate_argnums=(0,)).lower(state,
                                                        batch).compile()

    scan = compile_step()
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    fused = compile_step()
    assert "tpu_custom_call" not in scan.as_text()
    assert fused.as_text().count('custom_call_target="tpu_custom_call"') == 4
    assert fused.memory_analysis().temp_size_in_bytes < \
        scan.memory_analysis().temp_size_in_bytes / 2


def test_qwen3_moe_stage_fits_a_v5e_2x2(topo, monkeypatch):
    """qwen3moe-train-4k-2x2's train step, one 4096-token sequence per chip
    of a described 2x2 v5e host, with 32 experts held per chip: the
    compiler places it within a chip's 15.75 GiB, the experts move by
    all-to-all (no all-gather carries an expert leaf's f32[..,2048,2,768]
    or f32[..,768,2048] block), and the grouped products are ragged dots
    over a static buffer of every received slot, with no conditional: the
    step's work does not change with the routing.
    The program's backend check sees this CPU, so the test steers it to
    the fused attention the chip runs."""
    import re

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    step, state, batch = _cell_step(topo, "qwen3-30b-a3b-ep2x2", batch=4)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(state,
                                                        batch).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.75 * 2 ** 30
    text = compiled.as_text()
    assert "all-to-all" in text
    assert "ragged-dot" in text
    assert " conditional(" not in text
    gathers = [line for line in text.splitlines()
               if re.search(r"\ball-gather(-start)?\(", line)]
    assert gathers
    assert not any(re.search(r"2048,2,768\]|768,2048\]", line)
                   for line in gathers)
