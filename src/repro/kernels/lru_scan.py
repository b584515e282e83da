"""Pallas TPU blocked linear-recurrence scan (RG-LRU / mLSTM decay core).

h_t = a_t * h_{t-1} + x_t, elementwise over channels.  The time axis is
walked in (block_t) chunks along an ``arbitrary`` grid dimension; the carry
h lives in a VMEM scratch that persists across the time-grid steps, so HBM
traffic is exactly one read of (a, x) and one write of h — the memory-bound
roofline for this op.  Channels tile the lane dimension (128-aligned).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, o_ref, h_ref, *, block_t: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # one (1, block_c) row per step, read and written through the refs: a
    # dynamic row of a loaded value has no TPU lowering
    def step(t, h):
        row = pl.ds(t, 1)
        h = (h * a_ref[0, row, :].astype(jnp.float32)
             + x_ref[0, row, :].astype(jnp.float32))
        o_ref[0, row, :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = lax.fori_loop(0, block_t, step, h_ref[...])


def lru_scan_pallas(a: jax.Array, x: jax.Array, *, block_t: int = 256,
                    block_c: int = 128, interpret: bool = True) -> jax.Array:
    """a, x: (B, T, C) -> h: (B, T, C).  T % block_t == 0, C % block_c == 0
    (ops.py pads)."""
    B, T, C = a.shape
    block_t = min(block_t, T)
    block_c = min(block_c, C)
    assert T % block_t == 0 and C % block_c == 0
    grid = (B, C // block_c, T // block_t)
    return pl.pallas_call(
        functools.partial(_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_c), lambda b, c, t: (b, t, c)),
            pl.BlockSpec((1, block_t, block_c), lambda b, c, t: (b, t, c)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_c),
                               lambda b, c, t: (b, t, c)),
        out_shape=jax.ShapeDtypeStruct((B, T, C), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=interpret,
    )(a, x)
