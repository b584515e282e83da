"""Jit'd public wrappers: padding/layout glue around the Pallas kernels.

``interpret`` defaults to True on CPU (the validation mode for this
container) and False on TPU (real kernels).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_lse
from repro.kernels.lru_scan import lru_scan_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.quant import q4_matmul_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if not pad:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_offset: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, H, Tq, hd); k, v: (B, KV, Tkv, hd); fp32 products.
    Differentiable (fused backward kernels)."""
    interpret = _default_interpret() if interpret is None else interpret
    out, _ = flash_attention_lse(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_kv=block_kv,
        mxu=jnp.float32, interpret=interpret)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def matmul(a, b, *, block_m: int = 128, block_n: int = 128,
           block_k: int = 128, interpret: Optional[bool] = None):
    interpret = _default_interpret() if interpret is None else interpret
    M, K = a.shape
    N = b.shape[1]
    ap, _ = _pad_to(_pad_to(a, min(block_m, M) if M >= block_m else M, 0)[0],
                    block_k if K >= block_k else K, 1)
    bp, _ = _pad_to(_pad_to(b, block_k if K >= block_k else K, 0)[0],
                    block_n if N >= block_n else N, 1)
    out = matmul_pallas(ap, bp, block_m=block_m, block_n=block_n,
                        block_k=block_k, interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("group", "block_m", "block_n",
                                             "interpret"))
def q4_matmul(a, packed, scales, *, group: int = 32, block_m: int = 128,
              block_n: int = 128, interpret: Optional[bool] = None):
    """``a (M, K) @ dequantize_q4(packed (K//2, N), scales)`` fused.

    K must already divide by ``group`` (the quantizer enforces it); M, N
    and K are padded here.  Zero-padding N and K is sound because a padded
    column's or group's scale is zero, so its dequantized weights are
    exactly zero.
    """
    interpret = _default_interpret() if interpret is None else interpret
    M, K = a.shape
    N = packed.shape[1]
    half_group = group // 2
    bm = min(block_m, M)
    bn = block_n if N >= block_n else N
    bkh = min(128, K // 2)          # packed rows per k step: one lane tile
    if bkh % half_group:
        raise ValueError(f"group={group} must divide 2*block_kh={2 * bkh}")
    ae, _ = _pad_to(_pad_to(a[:, 0::2], bm, 0)[0], bkh, 1)
    ao, _ = _pad_to(_pad_to(a[:, 1::2], bm, 0)[0], bkh, 1)
    pp, _ = _pad_to(_pad_to(packed, bn, 1)[0], bkh, 0)
    sp, _ = _pad_to(_pad_to(scales, bn, 1)[0], bkh // half_group, 0)
    out = q4_matmul_pallas(ae, ao, pp, sp, group=group, block_m=bm,
                           block_n=bn, block_kh=bkh, interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("block_t", "block_c",
                                             "interpret"))
def lru_scan(a, x, *, block_t: int = 256, block_c: int = 128,
             interpret: Optional[bool] = None):
    """a, x: (B, T, C)."""
    interpret = _default_interpret() if interpret is None else interpret
    B, T, C = a.shape
    bt = min(block_t, T)
    bc = min(block_c, C)
    ap, _ = _pad_to(_pad_to(a, bt, 1)[0], bc, 2)
    xp, _ = _pad_to(_pad_to(x, bt, 1)[0], bc, 2)
    out = lru_scan_pallas(ap, xp, block_t=bt, block_c=bc,
                          interpret=interpret)
    return out[:, :T, :C]
