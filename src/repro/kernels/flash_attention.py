"""Pallas TPU flash attention (causal / sliding-window, GQA-native).

TPU adaptation of the online-softmax attention kernel: q is tiled into
(block_q, head_dim) VMEM blocks aligned to the MXU (128-multiples); the KV
stream is the innermost (``arbitrary``) grid dimension, one (block_kv,
head_dim) K and V tile per step, so fast memory holds a fixed few tiles at
any Tkv.  The fp32 running (m, l, o) live in VMEM scratch carried across
the KV steps.  GQA is expressed in the BlockSpec index maps: the kv-block
of q-head ``h`` is head ``h // group`` — no KV replication in HBM.  KV
blocks wholly masked for a q block skip their compute, and under
causality their index map repeats the last needed block, so they cost no
copy either.

Validated on CPU via interpret=True against kernels/ref.py (exact softmax).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: Optional[int], q_offset: int,
            scale: float):
    bq = q_ref.shape[1]
    bkv = k_ref.shape[1]
    qi, j = pl.program_id(1), pl.program_id(2)
    q_lo = q_offset + qi * bq                       # first q position
    k_lo = j * bkv                                  # first kv position

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = True
    if causal:
        needed = k_lo <= q_lo + bq - 1
    if window is not None:
        needed = jnp.logical_and(needed, q_lo - (k_lo + bkv - 1) < window)

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale           # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                   # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        qpos = q_lo + lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_lo + lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG)
        m = m_ref[...]                                     # (bq, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           window: Optional[int] = None,
                           q_offset: int = 0,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q: (B, H, Tq, hd); k, v: (B, KV, Tkv, hd).  Returns (B, H, Tq, hd).

    H % KV == 0 (GQA).  Tq % block_q == 0, Tkv % block_kv == 0 (pad in
    ops.py).  hd should be a multiple of 128 for MXU alignment on real TPUs
    (not enforced in interpret mode).
    """
    B, H, Tq, hd = q.shape
    KV, Tkv = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    group = H // KV
    block_q = min(block_q, Tq)
    block_kv = min(block_kv, Tkv)
    assert Tq % block_q == 0 and Tkv % block_kv == 0

    qr = q.reshape(B * H, Tq, hd)
    kr = k.reshape(B * KV, Tkv, hd)
    vr = v.reshape(B * KV, Tkv, hd)

    def kv_map(bh, qi, j):
        if causal:
            # past the last block this q block sees: re-use it (no copy)
            last = (q_offset + (qi + 1) * block_q - 1) // block_kv
            j = jnp.minimum(j, last)
        return bh // group, j, 0

    grid = (B * H, Tq // block_q, Tkv // block_kv)
    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window,
                          q_offset=q_offset, scale=1.0 / math.sqrt(hd)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, j: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, hd), kv_map),
            pl.BlockSpec((1, block_kv, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, j: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Tq, hd)
