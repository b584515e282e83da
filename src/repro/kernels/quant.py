"""Pallas TPU dequant-fused matmul over packed-int4 weights.

The ``q4_shared`` wire format ships weight windows as two int4 nibbles per
byte plus one f32 scale per length-``group`` run of K rows
(``repro.comm.quantize.quantize_q4``).  Dequantizing to a dense f32 weight
before the matmul would materialize 8x the gathered bytes in VMEM; this
kernel instead unpacks and rescales each (block_kh, block_n) packed tile
*inside* the matmul loop, so the packed bytes are what travels through the
memory hierarchy.

Grid ``(M / block_m, N / block_n, (K / 2) / block_kh)``: each k step takes
``block_kh`` packed rows, i.e. ``2 * block_kh`` K rows spanning
``2 * block_kh / group`` scale rows.  The activations arrive split into
their even and odd K columns (the low and high nibble of each byte), so
the kernel multiplies each nibble plane by its own half of ``a`` and never
interleaves rows.  Every block's last two dims are multiples of (8, 128)
or span the whole array, as the TPU's tiling requires for any ``group``
that divides ``2 * block_kh``; the scales stay whole along K, so one
(K / group, block_n) column of them is fetched per output column block.
fp32 accumulation in a VMEM scratch carried across the k dimension,
written out once on the last step — the same schedule as
``kernels.matmul``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ae_ref, ao_ref, p_ref, s_ref, o_ref, acc_ref, *, n_k: int,
            half_group: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pk = p_ref[...].astype(jnp.int32)                 # (block_kh, bn)
    bkh, bn = pk.shape
    n_g = bkh // half_group
    # each scale row covers half_group packed rows: repeat it down the block
    s = s_ref[pl.ds(k * n_g, n_g), :]                 # (n_g, bn)
    s = jnp.broadcast_to(s[:, None, :], (n_g, half_group, bn)).reshape(bkh, bn)
    lo = ((pk & 0xF) - 8).astype(jnp.float32) * s     # K rows 2r
    hi = ((pk >> 4) - 8).astype(jnp.float32) * s      # K rows 2r + 1
    acc_ref[...] += (
        jax.lax.dot(ae_ref[...].astype(jnp.float32), lo,
                    preferred_element_type=jnp.float32)
        + jax.lax.dot(ao_ref[...].astype(jnp.float32), hi,
                      preferred_element_type=jnp.float32))

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def q4_matmul_pallas(a_even: jax.Array, a_odd: jax.Array,
                     packed: jax.Array, scales: jax.Array, *,
                     group: int = 32, block_m: int = 128, block_n: int = 128,
                     block_kh: int = 128,
                     interpret: bool = True) -> jax.Array:
    """``a @ dequantize_q4(packed, scales)`` without densifying the weight,
    where ``a_even = a[:, 0::2]`` and ``a_odd = a[:, 1::2]``.

    ``a_even``, ``a_odd``: (M, K // 2); ``packed``: uint8 (K // 2, N);
    ``scales``: f32 (K // group, N).  M, N and K // 2 must divide by their
    blocks, and ``block_kh`` by ``group // 2`` (the jit wrapper pads).
    """
    M, Kh = a_even.shape
    N = packed.shape[1]
    half_group = group // 2
    assert a_odd.shape == (M, Kh) and packed.shape[0] == Kh
    assert scales.shape == (Kh // half_group, N)
    block_m, block_n, block_kh = (min(block_m, M), min(block_n, N),
                                  min(block_kh, Kh))
    assert M % block_m == 0 and N % block_n == 0 and Kh % block_kh == 0
    assert block_kh % half_group == 0, (block_kh, group)
    n_k = Kh // block_kh
    grid = (M // block_m, N // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, half_group=half_group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_kh), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, block_kh), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_kh, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((Kh // half_group, block_n),
                         lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a_even.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a_even, a_odd, packed, scales)
