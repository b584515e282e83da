"""The fused attention kernels (``kernels/flash_attention.py``) in interpret
mode against the KV-block scan of ``models/attention.py`` and the exact
softmax of ``kernels/ref.py``: output, log-sum-exp and the three gradients;
and the dispatch of ``models/attention.flash_attention`` between the two.

Shapes stay small (T <= 512) so that the interpreter keeps these quick; the
v5e compiles at the cells' widths are in ``test_tpu_compile.py``."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.attention as attention
from repro.kernels.flash_attention import flash_attention_lse
from repro.kernels.ref import attention_ref

HD = 128


def _inputs(seed, B, Tq, Tkv, H, KV, dtype):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)
    return (draw(B, Tq, H, HD), draw(B, Tkv, KV, HD), draw(B, Tkv, KV, HD),
            draw(B, Tq, H, HD).astype(jnp.float32),
            draw(B, H, Tq).astype(jnp.float32))


def _exact_lse(q, k, *, window, q_offset):
    H, KV = q.shape[2], k.shape[2]
    kq = jnp.repeat(k.astype(jnp.float32), H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kq,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(HD)
    qpos = q_offset + jnp.arange(q.shape[1])[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok &= qpos - kpos < window
    return jax.nn.logsumexp(jnp.where(ok, s, -1e30), axis=-1)


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


# (B, Tq, Tkv, H, KV, window, q_offset, input dtype, product dtype, tol)
CASES = {
    "mha": (1, 256, 256, 4, 4, None, 0, jnp.float32, jnp.float32, 2e-5),
    "gqa2-window": (1, 256, 256, 4, 2, 64, 0, jnp.float32, jnp.float32,
                    2e-5),
    "gqa4-q-offset": (1, 128, 384, 8, 2, None, 256, jnp.float32,
                      jnp.float32, 2e-5),
    "gqa9-ragged": (2, 200, 200, 9, 1, None, 0, jnp.float32, jnp.float32,
                    2e-5),
    "gqa4-window-bf16": (1, 384, 384, 4, 1, 100, 0, jnp.bfloat16,
                         jnp.bfloat16, 3e-2),
    "gqa2-ragged-bf16-products": (1, 320, 320, 4, 2, None, 0, jnp.float32,
                                  jnp.bfloat16, 3e-2),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_scan_and_exact(case):
    """Forward, log-sum-exp and dq/dk/dv from ``jax.grad`` of a loss of
    both, with 128-token blocks: several q and kv blocks, so causal and
    window block skips, the clamped index maps, padded tails and the GQA
    group sum all run."""
    B, Tq, Tkv, H, KV, window, q_off, dtype, mxu, tol = CASES[case]
    q, k, v, w, w_lse = _inputs(0, B, Tq, Tkv, H, KV, dtype)

    def fused(q, k, v):
        o, lse = flash_attention_lse(q, k, v, window=window,
                                     q_offset=q_off, block_q=128,
                                     block_kv=128, mxu=mxu)
        loss = jnp.sum(o.astype(jnp.float32) * w) + jnp.sum(lse * w_lse)
        return loss, (o, lse)

    def scan(q, k, v):
        o = attention.flash_attention(q, k, v, window=window,
                                      q_offset=q_off, block=64)
        lse = _exact_lse(q, k, window=window, q_offset=q_off)
        return jnp.sum(o.astype(jnp.float32) * w) + jnp.sum(lse * w_lse), o

    grad = jax.value_and_grad(fused, argnums=(0, 1, 2), has_aux=True)
    (_, (o, lse)), g = jax.jit(grad)(q, k, v)
    (_, o_scan), g_scan = jax.jit(jax.value_and_grad(
        scan, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    assert o.shape == q.shape and o.dtype == q.dtype
    assert lse.shape == (B, H, Tq) and lse.dtype == jnp.float32
    assert _gap(o, o_scan) < tol
    exact = attention_ref(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                          window=window, q_offset=q_off)
    assert _gap(o, exact.transpose(0, 2, 1, 3)) < tol
    assert _gap(lse, _exact_lse(q, k, window=window, q_offset=q_off)) < tol
    for name, a, b in zip("qkv", g, g_scan):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _gap(a, b) < tol, name


def test_remat_gives_the_same_gradients():
    """The kernels under ``jax.checkpoint``, as the layer remat and the
    ``remat_attn`` opt wrap them: the backward recomputes the forward
    kernel and gives the same gradients."""
    q, k, v, w, _ = _inputs(1, 1, 256, 256, 4, 2, jnp.float32)

    def loss(q, k, v):
        o = flash_attention_lse(q, k, v, block_q=128, block_kv=128)[0]
        return jnp.sum(o * w)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    g_remat = jax.jit(jax.grad(jax.checkpoint(loss),
                               argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g, g_remat):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _kernel_in_jaxpr(f, *args) -> bool:
    # a fresh function each time: a trace cached under another backend
    # check must not answer
    return "pallas_call" in str(jax.make_jaxpr(lambda *a: f(*a))(*args))


def test_dispatch_falls_back_to_the_scan(monkeypatch):
    """``models/attention.flash_attention`` takes the fused kernels only on
    a TPU, with head_dim a multiple of 128 and offsets known while
    tracing; otherwise the scan, unchanged."""
    q, k, v, _, _ = _inputs(2, 1, 256, 256, 4, 2, jnp.float32)

    def call(q, k, v, q_offset=0):
        return attention.flash_attention(q, k, v, q_offset=q_offset)

    # on this CPU: the scan
    assert not _kernel_in_jaxpr(call, q, k, v)
    # as on a TPU (traced only: the kernels cannot run here uninterpreted)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert _kernel_in_jaxpr(call, q, k, v)
    # an offset traced (a tensor-parallel rank): the scan
    assert not _kernel_in_jaxpr(call, q, k, v, jnp.int32(0))
    assert not _kernel_in_jaxpr(
        lambda q, k, v: attention.flash_attention(
            q, k, v, q_head_offset=jnp.int32(0)), q, k, v)
    # head_dim not a multiple of 128: the scan
    assert not _kernel_in_jaxpr(call, q[..., :64], k[..., :64],
                                v[..., :64])
    # a head shard (not all heads here): the scan
    assert not _kernel_in_jaxpr(
        lambda q, k, v: attention.flash_attention(q, k, v, H=8, kv_total=2),
        q, k, v)


def test_kernel_blocks_follow_the_shapes():
    assert attention._kernel_blocks(4096, 4096) == (1024, 1024)
    assert attention._kernel_blocks(200, 4096) == (256, 1024)
    assert attention._kernel_dtypes(jnp.float32, False) == (jnp.bfloat16,
                                                            jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        assert attention._kernel_dtypes(jnp.bfloat16, True) == (
            jnp.float32, jnp.bfloat16)
        assert attention._kernel_dtypes(jnp.float32, False) == (
            jnp.float32, jnp.float32)
