"""Pallas TPU flash attention with its own backward (causal / sliding-window,
GQA-native).

TPU adaptation of the online-softmax attention kernel and of the standard
flash-attention backward.  Three kernels, each a grid over (block_q,
head_dim) and (block_kv, head_dim) VMEM tiles aligned to the MXU
(128-multiples); every (block_q, block_kv) score tile lives and dies in
VMEM:

* **forward** (``flash_fwd``): grid (batch, q head, q block, kv block), the
  KV stream innermost (``arbitrary``); the fp32 running (m, l, o) live in
  VMEM scratch carried across the KV steps.  Besides the output it writes
  the fp32 log-sum-exp of each query row, the only residual the backward
  needs besides q, k, v and the output.
* **dq** (``flash_dq``): the same grid; recomputes each probability tile
  from q, k and the log-sum-exp and accumulates dq over the KV stream.
* **dk/dv** (``flash_dkv``): grid (batch, kv head, kv block, q head of the
  GQA group, q block), the last two innermost; each kv head's dk and dv
  sum the contributions of all q heads of its group in VMEM, so K and V
  are never replicated per q head in HBM.

Tensors keep the models' (batch, tokens, heads, head_dim) layout, viewed as
(batch, tokens, heads * head_dim): a head's tile is a column block, so no
transpose is needed on either side of the kernels.  GQA is expressed in the
index maps: the kv tile of q head ``h`` is that of head ``h // group``.  A
tile pair that is wholly masked (above the causal diagonal, or outside the
window) skips its compute, and the index maps clamp its block to the
nearest needed one, so it costs no copy either; only tiles that straddle a
mask boundary build the element mask.

Precision: every product takes its inputs in ``mxu`` (q·scale and k into
QKᵀ, dS with k or q into dq and dk) or ``pv`` (p and v into PV, dO with v
or p into dP and dV) and accumulates in fp32; on a TPU at JAX's default
precision both are bfloat16, exactly where a default-precision einsum
rounds.  m, l, the log-sum-exp, D = rowsum(dO ∘ O) and every accumulator
are fp32.

Validated on CPU via interpret=True against kernels/ref.py (exact softmax)
and against autodiff of the jnp attention of ``models/attention.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
LANES = 128           # row statistics are stored broadcast over one lane tile
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


@dataclasses.dataclass(frozen=True)
class _Plan:
    """Static shape of one attention call: masks, blocks, product dtypes."""
    causal: bool
    window: Optional[int]
    q_offset: int          # global position of query row 0
    kv_len: int            # real keys; positions from here on are padding
    n_q: int               # q blocks
    n_kv: int              # kv blocks
    block_q: int
    block_kv: int
    group: int             # q heads per kv head
    mxu: Any               # input dtype of QKᵀ, dS·K, dSᵀ·Q
    pv: Any                # input dtype of P·V, dO·Vᵀ, Pᵀ·dO
    interpret: bool

    # -- which tiles hold an unmasked (query, key) pair -------------------
    def _bounds(self, qi, j):
        q_lo = self.q_offset + qi * self.block_q
        k_lo = j * self.block_kv
        return q_lo, q_lo + self.block_q - 1, k_lo, k_lo + self.block_kv - 1

    def needed(self, qi, j):
        q_lo, q_hi, k_lo, k_hi = self._bounds(qi, j)
        ok = True
        if self.causal:
            ok = k_lo <= q_hi
        if self.window is not None:
            ok = jnp.logical_and(ok, q_lo - k_hi < self.window)
        return ok

    def masked(self, qi, j):
        """Whether the tile needs the element mask (it straddles a mask
        boundary or holds padded keys)."""
        q_lo, q_hi, k_lo, k_hi = self._bounds(qi, j)
        out = k_hi >= self.kv_len
        if self.causal:
            out = jnp.logical_or(out, k_hi > q_lo)
        if self.window is not None:
            out = jnp.logical_or(out, q_hi - k_lo >= self.window)
        return out

    def mask(self, qi, j):
        q_lo, _, k_lo, _ = self._bounds(qi, j)
        shape = (self.block_q, self.block_kv)
        qpos = q_lo + lax.broadcasted_iota(jnp.int32, shape, 0)
        kpos = k_lo + lax.broadcasted_iota(jnp.int32, shape, 1)
        ok = kpos < self.kv_len
        if self.causal:
            ok &= kpos <= qpos
        if self.window is not None:
            ok &= (qpos - kpos) < self.window
        return ok

    # -- index-map clamps: a skipped tile re-uses a needed block ----------
    def kv_block(self, qi, j):
        q_lo, q_hi, _, _ = self._bounds(qi, 0)
        hi = self.n_kv - 1
        if self.causal:
            hi = jnp.minimum(q_hi // self.block_kv, hi)
        lo = 0
        if self.window is not None:
            lo = jnp.minimum(
                jnp.maximum((q_lo - self.window + 1) // self.block_kv, 0), hi)
        return jnp.minimum(jnp.maximum(j, lo), hi)

    def q_block(self, j, qi):
        _, _, k_lo, k_hi = self._bounds(0, j)
        lo = 0
        if self.causal:
            lo = jnp.minimum(
                jnp.maximum((k_lo - self.q_offset) // self.block_q, 0),
                self.n_q - 1)
        hi = self.n_q - 1
        if self.window is not None:
            hi = jnp.maximum(jnp.minimum(
                (k_hi + self.window - 1 - self.q_offset) // self.block_q, hi),
                lo)
        return jnp.minimum(jnp.maximum(qi, lo), hi)

    def dot(self, a, b, dims, dtype):
        prec = lax.Precision.HIGHEST if dtype == jnp.float32 else None
        return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               precision=prec,
                               preferred_element_type=jnp.float32)


def _rows(ref, width: int):
    """A row statistic stored broadcast over ``LANES`` lanes, as (rows,
    width) for a (rows, width) tile."""
    x = ref[0, 0]
    if width % LANES == 0:
        return jnp.tile(x, (1, width // LANES))
    return x[:, :1]


def _scores(plan, q_ref, k_ref, qi, j, masked, scale):
    """The tile's scaled scores, masked where ``masked``, and the scaled
    q it was made from."""
    qs = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    s = plan.dot(qs, k_ref[0], _NT, plan.mxu)
    if masked:
        s = jnp.where(plan.mask(qi, j), s, NEG)
    return s, qs


def _when_needed(plan, qi, j, step):
    """Run ``step(masked)`` on a needed tile, with the element mask only on
    tiles that straddle a mask boundary."""
    needed, masked = plan.needed(qi, j), plan.masked(qi, j)

    @pl.when(jnp.logical_and(needed, masked))
    def _edge():
        step(True)

    @pl.when(jnp.logical_and(needed, jnp.logical_not(masked)))
    def _inner():
        step(False)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                plan: _Plan, scale: float):
    qi, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        s, _ = _scores(plan, q_ref, k_ref, qi, j, masked, scale)
        m = m_sc[...]                                      # (bq, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + plan.dot(p, v_ref[0], _NN,
                                                     plan.pv)
        m_sc[...] = m_new

    _when_needed(plan, qi, j, step)

    @pl.when(j == plan.n_kv - 1)
    def _done():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_sc[...] + jnp.log(l),
                                         lse_ref.shape[2:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, dq_sc, *,
               plan: _Plan, scale: float):
    qi, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(masked):
        s, _ = _scores(plan, q_ref, k_ref, qi, j, masked, scale)
        p = jnp.exp(s - _rows(lse_ref, plan.block_kv))
        dp = plan.dot(do_ref[0], v_ref[0], _NT, plan.pv)
        ds = p * (dp - _rows(d_ref, plan.block_kv))
        dq_sc[...] += plan.dot(ds, k_ref[0], _NN, plan.mxu)

    _when_needed(plan, qi, j, step)

    @pl.when(j == plan.n_kv - 1)
    def _done():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, plan: _Plan, scale: float):
    j, g, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        s, qs = _scores(plan, q_ref, k_ref, qi, j, masked, scale)
        p = jnp.exp(s - _rows(lse_ref, plan.block_kv))
        do = do_ref[0]
        dv_sc[...] += plan.dot(p.T, do, _NN, plan.pv)
        dp = plan.dot(do, v_ref[0], _NT, plan.pv)
        ds = p * (dp - _rows(d_ref, plan.block_kv))
        dk_sc[...] += plan.dot(ds.T, qs, _NN, plan.mxu)

    _when_needed(plan, qi, j, step)

    @pl.when(jnp.logical_and(g == plan.group - 1, qi == plan.n_q - 1))
    def _done():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Calls: (B, T, heads * hd) views, row statistics (B, H, Tq, LANES)
# ---------------------------------------------------------------------------

def _flat(x):
    """(B, T, heads, hd) as (B, T, heads * hd): a head is a column block."""
    return x.reshape(*x.shape[:2], -1)


def _call(plan: _Plan, kernel, name: str, hd: int, grid, in_specs,
          out_specs, out_shape, scratch):
    return pl.pallas_call(
        functools.partial(kernel, plan=plan, scale=1.0 / math.sqrt(hd)),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3
            + ("arbitrary",) * (len(grid) - 3)),
        interpret=plan.interpret, name=name)


def _q_major(plan: _Plan, hd: int):
    """Block specs of a (batch, q head, q block, kv block) grid: a q-side
    tile, a kv-side tile, a row statistic."""
    return (pl.BlockSpec((1, plan.block_q, hd),
                         lambda b, h, qi, j: (b, qi, h)),
            pl.BlockSpec((1, plan.block_kv, hd),
                         lambda b, h, qi, j: (b, plan.kv_block(qi, j),
                                              h // plan.group)),
            pl.BlockSpec((1, 1, plan.block_q, LANES),
                         lambda b, h, qi, j: (b, h, qi, 0)))


def _kv_major(plan: _Plan, hd: int):
    """Block specs of a (batch, kv head, kv block, group head, q block)
    grid: a q-side tile, a kv-side tile, a row statistic."""
    def head(c, g):
        return c * plan.group + g
    return (pl.BlockSpec((1, plan.block_q, hd),
                         lambda b, c, j, g, qi: (b, plan.q_block(j, qi),
                                                 head(c, g))),
            pl.BlockSpec((1, plan.block_kv, hd),
                         lambda b, c, j, g, qi: (b, j, c)),
            pl.BlockSpec((1, 1, plan.block_q, LANES),
                         lambda b, c, j, g, qi: (b, head(c, g),
                                                 plan.q_block(j, qi), 0)))


def _fwd(plan: _Plan, q, k, v):
    B, Tq, H, hd = q.shape
    qs, kvs, stat = _q_major(plan, hd)
    o, lse = _call(
        plan, _fwd_kernel, "flash_fwd", hd, (B, H, plan.n_q, plan.n_kv),
        [qs, kvs, kvs], [qs, stat],
        [jax.ShapeDtypeStruct((B, Tq, H * hd), q.dtype),
         jax.ShapeDtypeStruct((B, H, Tq, LANES), jnp.float32)],
        [pltpu.VMEM((plan.block_q, 1), jnp.float32),
         pltpu.VMEM((plan.block_q, 1), jnp.float32),
         pltpu.VMEM((plan.block_q, hd), jnp.float32)],
    )(_flat(q), _flat(k), _flat(v))
    return o.reshape(q.shape), lse


def _dq(plan: _Plan, q, k, v, do, lse, d):
    B, Tq, H, hd = q.shape
    qs, kvs, stat = _q_major(plan, hd)
    dq = _call(
        plan, _dq_kernel, "flash_dq", hd, (B, H, plan.n_q, plan.n_kv),
        [qs, kvs, kvs, qs, stat, stat], qs,
        jax.ShapeDtypeStruct((B, Tq, H * hd), q.dtype),
        [pltpu.VMEM((plan.block_q, hd), jnp.float32)],
    )(_flat(q), _flat(k), _flat(v), _flat(do), lse, d)
    return dq.reshape(q.shape)


def _dkv(plan: _Plan, q, k, v, do, lse, d):
    B, Tkv, KV, hd = k.shape
    qs, kvs, stat = _kv_major(plan, hd)
    dk, dv = _call(
        plan, _dkv_kernel, "flash_dkv", hd,
        (B, KV, plan.n_kv, plan.group, plan.n_q),
        [qs, kvs, kvs, qs, stat, stat], [kvs, kvs],
        [jax.ShapeDtypeStruct((B, Tkv, KV * hd), k.dtype),
         jax.ShapeDtypeStruct((B, Tkv, KV * hd), v.dtype)],
        [pltpu.VMEM((plan.block_kv, hd), jnp.float32),
         pltpu.VMEM((plan.block_kv, hd), jnp.float32)],
    )(_flat(q), _flat(k), _flat(v), _flat(do), lse, d)
    return dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attention(plan: _Plan, q, k, v):
    return _fwd(plan, q, k, v)


def _attention_fwd(plan: _Plan, q, k, v):
    o, lse = _fwd(plan, q, k, v)
    return (o, lse), (q, k, v, o, lse)


def _attention_bwd(plan: _Plan, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    B, Tq, H, _ = q.shape
    # dS = P ∘ (dP - D) with D = rowsum(dO ∘ O); a log-sum-exp cotangent
    # adds P ∘ dlse, i.e. subtracts dlse from D
    d = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    d = d.transpose(0, 2, 1)[..., None] - dlse[..., :1]
    d = jnp.broadcast_to(d, (B, H, Tq, LANES))
    dq = _dq(plan, q, k, v, do, lse, d)
    dk, dv = _dkv(plan, q, k, v, do, lse, d)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)


def _pad(x, block: int):
    pad = (-x.shape[1]) % block
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: Optional[int] = None,
                        q_offset: int = 0, block_q: int = 512,
                        block_kv: int = 512, mxu=jnp.float32, pv=None,
                        interpret: bool = True
                        ) -> tuple[jax.Array, jax.Array]:
    """Attention and its fp32 log-sum-exp per (batch, head, query).

    q: (B, Tq, H, hd); k, v: (B, Tkv, KV, hd) with H % KV == 0 (GQA: q head
    ``h`` reads kv head ``h // (H // KV)``).  Query row ``i`` sits at
    position ``q_offset + i``, key ``j`` at ``j``.  Returns (B, Tq, H, hd)
    and (B, H, Tq).  Differentiable in q, k and v through the fused
    backward kernels.  Tq and Tkv are padded here to whole blocks (padded
    keys are masked); ``mxu`` and ``pv`` are the product input dtypes (see
    the module docstring; ``pv`` defaults to ``mxu``).
    """
    B, Tq, H, hd = q.shape
    Tkv, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"{H} q heads do not group over {KV} kv heads")
    block_q, block_kv = min(block_q, Tq), min(block_kv, Tkv)
    qp, kp, vp = _pad(q, block_q), _pad(k, block_kv), _pad(v, block_kv)
    plan = _Plan(causal=causal, window=window, q_offset=q_offset,
                 kv_len=Tkv, n_q=qp.shape[1] // block_q,
                 n_kv=kp.shape[1] // block_kv, block_q=block_q,
                 block_kv=block_kv, group=H // KV,
                 mxu=jnp.dtype(mxu), pv=jnp.dtype(pv or mxu),
                 interpret=interpret)
    o, lse = _attention(plan, qp, kp, vp)
    return o[:, :Tq], lse[:, :, :Tq, 0]

