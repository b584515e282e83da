"""Attention: blocked online-softmax (flash-style, pure jnp) + parallel modes.

Two sharded modes (chosen per arch by head divisibility):
  * head_tp — q heads sharded over tp; x all-gathered, out reduce-scattered
              (Megatron-SP).  Requires H % tp == 0; kv heads are
              replicated-compute when kv % tp != 0 (GQA: kv tiny).
  * cp      — context parallel: tokens stay sequence-sharded; full KV is
              all-gathered (small for GQA); q-chunk attention is local.
              Works for ANY head count — the universal fallback.

Decode uses split-K: the KV cache is T-sharded over tp, each chip computes a
partial softmax over its chunk, merged with a logsumexp psum (FlashDecoding).

The KV-block scan body is counted once by HLO cost analysis; the roofline adds
the analytic attention-FLOP correction (``attn_flops``).

On a TPU the attention core runs as the fused Pallas kernels of
``kernels/flash_attention.py`` (forward and backward, score tiles kept in
VMEM) wherever the call allows it; everywhere else it is the KV-block scan.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.flash_attention import flash_attention_lse
from repro.models.layers import rms_norm, rope
from repro.models.parallel import ParallelCtx

NEG = -1e30


def _kv_head_map(nq_local: int, q_head_offset, H: int, kv: int,
                 kv_head_offset=0):
    """kv-head index (local to the kv shard) for each local q head."""
    group = H // kv
    return (q_head_offset + jnp.arange(nq_local)) // group - kv_head_offset


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_dtypes(dtype, bf16_probs: bool):
    """Input dtypes of the fused kernels' products (``mxu``, ``pv``): those
    a TPU einsum at JAX's default matmul precision rounds to (bfloat16),
    full fp32 where a finer precision is configured; with ``bf16_probs``
    the probabilities and V enter their products in the compute dtype."""
    single_pass = (None, "default", "bfloat16", "BF16_BF16_F32")
    if jax.config.jax_default_matmul_precision in single_pass:
        return jnp.bfloat16, jnp.bfloat16
    pv = dtype if bf16_probs else jnp.float32
    return jnp.float32, pv


def _kernel_blocks(Tq: int, Tkv: int) -> tuple[int, int]:
    """(block_q, block_kv) of the fused kernels: 1024-token tiles cut to the
    sequence rounded up to a lane tile.  On a v5e at 4096 tokens and
    head_dim 128 (36 / 4 heads), 1024 x 1024 tiles ran forward and
    backward in 7.0 ms against 8.7 at 512 x 512; 2048-token tiles run out
    of fast memory."""
    return tuple(min(1024, -(-t // 128) * 128) for t in (Tq, Tkv))


def _kernel_applies(nq: int, kv: int, hd: int, H: int, kv_total: int,
                    offsets) -> bool:
    """Whether this call runs as the fused kernels: on a TPU, with heads a
    whole number of lane tiles, offsets known while tracing, and all heads
    here (no head shard to map)."""
    return (_on_tpu() and hd % 128 == 0
            and all(type(o) is int for o in offsets)
            and offsets[1:] == (0, 0) and nq == H and kv == kv_total)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset=0, q_head_offset=0, kv_head_offset=0,
                    H: Optional[int] = None, kv_total: Optional[int] = None,
                    block: int = 1024, bf16_probs: bool = False) -> jax.Array:
    """q: (B, Tq, nq, hd); k, v: (B, Tkv, kv, hd) (full KV).

    ``q_offset``: global position of q[.., 0, ..] (sequence-parallel chunk);
    ``q_head_offset``: global head index of q head 0 (head-parallel shard).
    On a TPU, where :func:`_kernel_applies`, the fused Pallas kernels with
    their own backward; otherwise online softmax over KV blocks of
    ``block`` keys — memory O(Tq * block).
    """
    B, Tq, nq, hd = q.shape
    Tkv, kv = k.shape[1], k.shape[2]
    H = H if H is not None else nq
    if _kernel_applies(nq, kv, hd, H, kv_total or kv,
                       (q_offset, q_head_offset, kv_head_offset)):
        mxu, pv = _kernel_dtypes(q.dtype, bf16_probs)
        bq, bkv = _kernel_blocks(Tq, Tkv)
        return flash_attention_lse(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            block_q=bq, block_kv=bkv, mxu=mxu, pv=pv, interpret=False)[0]
    scale = 1.0 / math.sqrt(hd)
    block = min(block, Tkv)
    pad = (-Tkv) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = (Tkv + pad) // block

    kvmap = _kv_head_map(nq, q_head_offset, H, kv_total or kv,
                         kv_head_offset)                   # (nq,)
    qpos = q_offset + jnp.arange(Tq)                       # (Tq,)

    kb = k.reshape(B, n_blocks, block, kv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, block, kv, hd).transpose(1, 0, 2, 3, 4)
    qf = (q * scale).astype(jnp.float32)

    def body(carry, inp):
        o, m, l = carry
        bidx, kblk, vblk = inp
        kpos = bidx * block + jnp.arange(block)            # (block,)
        kq = jnp.take(kblk, kvmap, axis=2)                 # (B, block, nq, hd)
        vq = jnp.take(vblk, kvmap, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kq.astype(jnp.float32))
        mask = kpos[None, :] < Tkv                         # padding
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        if bf16_probs:
            # §Perf opt: the (bq, block)-sized probabilities move to the PV
            # matmul in bf16 (fp32 row stats m/l keep the softmax exact).
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype),
                            vq.astype(q.dtype),
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bhqk,bkhd->bhqd", p, vq.astype(jnp.float32))
        o_new = o * alpha[..., None] + pv
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((B, nq, Tq, hd), jnp.float32)
    m0 = jnp.full((B, nq, Tq), NEG, jnp.float32)
    l0 = jnp.zeros((B, nq, Tq), jnp.float32)
    (o, m, l), _ = lax.scan(body, (o0, m0, l0),
                            (jnp.arange(n_blocks), kb, vb))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)       # (B, Tq, nq, hd)


def attn_flops(B: int, Tq: int, Tkv: int, H: int, hd: int, *,
               causal: bool, window: Optional[int]) -> float:
    """Analytic matmul FLOPs of one attention call (QK^T + PV), global."""
    if window is not None:
        eff = min(window, Tkv)
        pairs = B * Tq * eff
    elif causal and Tq == Tkv:
        pairs = B * Tq * (Tq + 1) // 2
    else:
        pairs = B * Tq * Tkv
    return 4.0 * pairs * H * hd


# ---------------------------------------------------------------------------
# Train/prefill block
# ---------------------------------------------------------------------------

@jax.named_scope("attn")
def attn_block(x_sp: jax.Array, p: dict, meta: dict, ctx: ParallelCtx, cfg, *,
               mode: str, window: Optional[int], t_offset: int = 0,
               return_kv: bool = False):
    """x_sp: (B, T/tp, d).  Returns new x_sp (and this layer's (k, v) local
    T-chunk when ``return_kv`` — used by prefill to build the cache)."""
    H, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    eps = cfg.norm_eps
    B, T_loc, d = x_sp.shape
    # a tensor-parallel axis of size 1 has rank 0: known while tracing, so
    # the offsets below stay static and the fused kernels can take the call
    tp_rank = ctx.tp_rank if ctx.tp > 1 else 0
    h = rms_norm(x_sp, ctx.gather_w(p["ln"], meta["ln"].fsdp_dim), eps)

    wq = ctx.gather_w(p["wq"], meta["wq"].fsdp_dim)
    wkv = ctx.gather_w(p["wkv"], meta["wkv"].fsdp_dim)
    wo = ctx.gather_w(p["wo"], meta["wo"].fsdp_dim)

    if mode == "head_tp":
        hg = ctx.ag_tokens(h)                               # (B, T, d)
        T = hg.shape[1]
        q = (hg @ wq).reshape(B, T, H // ctx.tp, hd)
        kvp = jnp.einsum("btd,dgk->btgk", hg, wkv)
        kvp = kvp.reshape(B, T, 2, wkv.shape[-1] // hd, hd)
        q_off, q_hoff = 0, tp_rank * (H // ctx.tp)
    else:  # cp
        q = (h @ wq).reshape(B, T_loc, H, hd)
        kvp = jnp.einsum("btd,dgk->btgk", h, wkv)
        kvp = kvp.reshape(B, T_loc, 2, kv, hd)
        q_off, q_hoff = tp_rank * T_loc, 0
    k, v = kvp[:, :, 0], kvp[:, :, 1]

    if cfg.qk_norm:
        q = rms_norm(q, ctx.gather_w(p["q_norm"], meta["q_norm"].fsdp_dim),
                     eps)
        k = rms_norm(k, ctx.gather_w(p["k_norm"], meta["k_norm"].fsdp_dim),
                     eps)
    if cfg.pos == "rope":
        rdt = ctx.compute_dtype if ctx.has("bf16_rope") else None
        tq = t_offset + q_off + jnp.arange(q.shape[1])
        tk = t_offset + (jnp.arange(k.shape[1]) if mode == "head_tp"
                         else q_off + jnp.arange(T_loc))
        q = rope(q, tq, cfg.rope_theta, rdt)
        k = rope(k, tk, cfg.rope_theta, rdt)

    k_loc, v_loc = k, v  # this chip's T-chunk (cp) / full (head_tp)
    if mode == "cp":
        k = ctx.ag_tokens(k)                                # (B, T, kv, hd)
        v = ctx.ag_tokens(v)
        q_pos_off = t_offset + q_off
    else:
        q_pos_off = t_offset
    kv_local = k.shape[2]
    kv_hoff = tp_rank * kv_local if kv_local != kv else 0

    import functools as _ft
    attn_f = _ft.partial(flash_attention, causal=True, window=window,
                         q_offset=q_pos_off, q_head_offset=q_hoff,
                         kv_head_offset=kv_hoff, H=H, kv_total=kv,
                         bf16_probs=ctx.has("bf16_probs"))
    if ctx.has("remat_attn"):
        # §Perf opt: recompute attention in the bwd instead of saving the
        # per-block fp32 intermediates from the fwd residuals.
        attn_f = jax.checkpoint(attn_f)
    o = attn_f(q, k, v)
    o = o.reshape(o.shape[0], o.shape[1], -1)
    if mode == "head_tp":
        # output projection through the fused rs_tokens fast path: with the
        # "overlap" opt the SP reduce-scatter streams behind the matmul;
        # without it this is exactly rs_tokens(o @ wo)
        out = x_sp + ctx.matmul_rs(o, wo)
        if return_kv:
            # cache stores the T-sharded chunk: slice mine from full k, v
            k_loc = lax.dynamic_slice_in_dim(k, tp_rank * T_loc, T_loc, 1)
            v_loc = lax.dynamic_slice_in_dim(v, tp_rank * T_loc, T_loc, 1)
    else:
        out = x_sp + o @ wo
    if return_kv:
        return out, (k_loc, v_loc)
    return out


# ---------------------------------------------------------------------------
# Decode (split-K over the T-sharded cache)
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     ctx: ParallelCtx, *, pos, H: int,
                     window: Optional[int] = None,
                     ring: bool = False) -> jax.Array:
    """q: (B, 1, H, hd) (all heads, replicated-compute);
    k/v_cache: (B, S/tp, kv, hd) local chunk.  ``pos``: current global
    position — a scalar shared by the batch, or a (B,) vector of per-slot
    positions (continuous batching over heterogeneous sequence lengths).
    ``ring``: cache is a ring buffer of size ``window`` (global kv index =
    pos - window + 1 .. pos, stored mod window)."""
    B, _, nH, hd = q.shape
    S_loc, kv = k_cache.shape[1], k_cache.shape[2]
    scale = 1.0 / math.sqrt(hd)
    base = ctx.tp_rank * S_loc
    slot = base + jnp.arange(S_loc)                         # local slots
    pos = jnp.asarray(pos)
    if pos.ndim == 1:                    # per-slot positions: (B, 1)
        pos = pos[:, None]               # broadcasts against slot (S_loc,)
    if ring:
        W = window
        # slot s holds global index: the largest g <= pos with g % W == s
        gidx = pos - ((pos - slot) % W)
        valid = (gidx >= 0) & (gidx <= pos) & (pos - gidx < W)
    else:
        gidx = slot
        valid = gidx <= pos
        if window is not None:
            valid &= (pos - gidx) < window

    kvmap = _kv_head_map(nH, 0, H, kv)
    kq = jnp.take(k_cache, kvmap, axis=2).astype(jnp.float32)
    vq = jnp.take(v_cache, kvmap, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, kq)
    mask = valid if valid.ndim == 2 else valid[None]        # (B | 1, S_loc)
    s = jnp.where(mask[:, None, None, :], s, NEG)
    m = jnp.max(s, axis=-1)                                 # (B, H, 1)
    M = ctx.pmax_tp(m)
    p = jnp.exp(s - M[..., None])
    l = ctx.psum_tp(jnp.sum(p, axis=-1))
    o = ctx.psum_tp(jnp.einsum("bhqk,bkhd->bhqd", p, vq))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)        # (B, 1, H, hd)


def cache_write(cache: jax.Array, new: jax.Array, ctx: ParallelCtx, *, pos,
                window: Optional[int] = None) -> jax.Array:
    """Write (B, 1, kv, hd) into the T-sharded (B, S/tp, kv, hd) cache at
    global position ``pos`` — a shared scalar or a (B,) vector of per-slot
    positions (ring-buffer when ``window``).  Every chip computes the same
    ``new``; only the owner's mask hits."""
    S_loc = cache.shape[1]
    pos = jnp.asarray(pos)
    gpos = pos % window if window is not None else pos
    owner = gpos // S_loc
    local = gpos - owner * S_loc
    if pos.ndim == 1:                    # per-slot positions: (B, S_loc)
        hit = jnp.arange(S_loc)[None, :] == local[:, None]
        if ctx.tp_axis:
            hit &= (ctx.tp_rank == owner)[:, None]
    else:
        hit = (jnp.arange(S_loc) == local) & (ctx.tp_rank == owner) \
            if ctx.tp_axis else (jnp.arange(S_loc) == local)
        hit = hit[None]
    return jnp.where(hit[:, :, None, None], new.astype(cache.dtype), cache)
