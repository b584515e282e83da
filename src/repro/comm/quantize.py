"""Quantized wire-format collective bodies (int8 / bf16 / packed int4).

The paper's C1 invariant attacks the *resident* bytes of a collective;
this module attacks the *wire* bytes on the slow bridge tier, where the
hierarchical decomposition concentrates all inter-node traffic.  Every
body here keeps the on-node stages full precision — only the payload that
actually crosses ``slow_axis`` is compressed — so the shared window a
``shared``-class result hands out stays exact.

Layering: the registry schemes in ``repro.comm.registry`` (``q8_hier``,
``qbf16_hier``, ``q4_shared``) bind these bodies; call sites reach them
only through ``Communicator(..., precision="lossy")``.  The deprecated
free functions in ``repro.optim.compression`` shim onto the same cores.

Quantization model (per-block symmetric):

* the payload is flattened and cut into ``block``-sized blocks, each with
  its own f32 scale ``amax / qmax`` — an outlier only collapses its own
  block, not the whole tensor;
* for *psum* payloads the wire schedule is picked by the bridge's rank
  count: small-world bridges (<= 3 ranks) fuse int8 codes + LOCAL scales
  into ONE u8 gather summed locally in f32; wider bridges share block
  scales with one tiny ``lax.pmax`` (so every rank quantizes onto the
  same grid and the int16 wire sum is exact for <= 256 pods:
  127 * 256 < 2**15);
* for *gather* payloads scales stay local and travel with the data;
* error feedback: the psum cores optionally take the previous step's
  residual (``err``) and return the new local quantization residual —
  local, never the divergent global total (see PR 6).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .primitives import _axes, axis_index, scoped
from repro.substrate.compat import axis_size

DEFAULT_BLOCK = 256
Q8_MAX = 127.0
Q4_MAX = 7.0
_EPS = 1e-30


# ---------------------------------------------------------------------------
# Per-block quantize / dequantize cores
# ---------------------------------------------------------------------------

def _to_blocks(x: jax.Array, block: int) -> tuple[jax.Array, int, int]:
    """Flatten ``x`` to f32 ``(n_blocks, block_eff)``; zero-pad the tail.

    Returns ``(blocks, size, block_eff)``.  ``block_eff`` shrinks to the
    flat size for tensors smaller than one block (per-tensor scale, the
    pre-fix behaviour, which is exact there).  Padding zeros quantize to
    zero and are sliced off after dequantization.
    """
    flat = x.astype(jnp.float32).reshape(-1)
    size = flat.shape[0]
    block_eff = max(1, min(int(block), size))
    pad = (-size) % block_eff
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block_eff), size, block_eff


def _from_blocks(blocks: jax.Array, size: int, shape, dtype) -> jax.Array:
    return blocks.reshape(-1)[:size].reshape(shape).astype(dtype)


def block_quantize(x: jax.Array, *, block: int = DEFAULT_BLOCK,
                   qmax: float = Q8_MAX, shared_axes=(),
                   stochastic: bool = False,
                   key: Optional[jax.Array] = None):
    """Per-block symmetric quantization of ``x``.

    Returns ``(q, scale, meta)`` where ``q`` is int8 ``(n_blocks, block)``,
    ``scale`` is f32 ``(n_blocks,)`` and ``meta = (size, block_eff)`` for
    :func:`block_dequantize`.  ``shared_axes`` max-reduces the block amax
    across ranks first (psum payloads must share one grid).
    """
    blocks, size, block_eff = _to_blocks(x, block)
    amax = jnp.max(jnp.abs(blocks), axis=1)
    if shared_axes:
        amax = scoped(lax.pmax, amax, _axes(shared_axes))
    scale = jnp.maximum(amax, _EPS) / qmax
    scaled = blocks / scale[:, None]
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        noise = jax.random.uniform(key, scaled.shape)
        q = jnp.floor(scaled + noise)
    else:
        q = jnp.round(scaled)
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    return q, scale, (size, block_eff)


def block_dequantize(q: jax.Array, scale: jax.Array, meta, shape,
                     dtype=jnp.float32) -> jax.Array:
    size, _ = meta
    blocks = q.astype(jnp.float32) * scale[:, None]
    return _from_blocks(blocks, size, shape, dtype)


# ---------------------------------------------------------------------------
# Packed-int4 codec (two nibbles per uint8)
# ---------------------------------------------------------------------------

def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int8 values in ``[-7, 7]`` two-per-byte along the last axis.

    Values are biased to ``[1, 15]`` (0 is never produced, so an all-zero
    byte can only mean padding).  The last axis extent must be even.
    """
    if q.shape[-1] % 2:
        raise ValueError(f"int4 pack needs an even extent, got {q.shape}")
    b = (q.astype(jnp.int32) + 8).astype(jnp.uint8)
    lo, hi = b[..., 0::2], b[..., 1::2]
    return lo | (hi << 4)


def unpack_int4(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: uint8 ``(..., n)`` -> int8 ``(..., 2n)``."""
    lo = (p & 0xF).astype(jnp.int8) - 8
    hi = (p >> 4).astype(jnp.int8) - 8
    pairs = jnp.stack([lo, hi], axis=-1)
    return pairs.reshape(p.shape[:-1] + (2 * p.shape[-1],))


def quantize_q4(w: jax.Array, *, group: int = 32):
    """Groupwise-K int4 weight quantization for the ``ag_matmul`` fast path.

    ``w`` is a ``(K, N)`` panel; each length-``group`` run of K rows in a
    column shares one f32 scale.  Returns ``(packed, scales)`` with
    ``packed`` uint8 ``(K // 2, N)`` (nibble pairs along K) and ``scales``
    f32 ``(K // group, N)``.
    """
    k, n = w.shape
    if group % 2 or k % group:
        raise ValueError(f"K={k} must divide into even groups of {group}")
    g = w.astype(jnp.float32).reshape(k // group, group, n)
    amax = jnp.max(jnp.abs(g), axis=1)
    scales = jnp.maximum(amax, _EPS) / Q4_MAX
    q = jnp.clip(jnp.round(g / scales[:, None, :]), -Q4_MAX, Q4_MAX)
    q = q.astype(jnp.int8).reshape(k, n)
    # pack along K: byte r holds rows (2r, 2r+1)
    b = (q.astype(jnp.int32) + 8).astype(jnp.uint8)
    packed = b[0::2, :] | (b[1::2, :] << 4)
    return packed, scales


def dequantize_q4(packed: jax.Array, scales: jax.Array, *,
                  group: int = 32, dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_q4` -> ``(K, N)`` in ``dtype``."""
    k2, n = packed.shape
    lo = (packed & 0xF).astype(jnp.int8) - 8
    hi = (packed >> 4).astype(jnp.int8) - 8
    q = jnp.stack([lo, hi], axis=1).reshape(2 * k2, n)
    g = q.astype(jnp.float32).reshape(-1, group, n)
    return (g * scales[:, None, :]).reshape(2 * k2, n).astype(dtype)


# ---------------------------------------------------------------------------
# Quantized psum cores (gradient-bridge wire formats)
# ---------------------------------------------------------------------------

def _axes_count(axes) -> int:
    """Static rank count of a (possibly empty) axis-name tuple."""
    n = 1
    for a in axes:
        n *= int(axis_size(a))
    return n


def q8_psum_flat(x: jax.Array, axes, *, block: int = DEFAULT_BLOCK,
                 err: Optional[jax.Array] = None,
                 stochastic: bool = False, key=None):
    """int8-on-the-wire psum of ``x`` over ``axes``.

    The whole reduction is treated as one bridge, with two wire schedules
    picked statically by the bridge's rank count ``p``:

    * ``p <= 3`` (the small-world bridge): ONE tiled ``u8`` all-gather of
      a fused buffer — int8 codes followed by the rank's LOCAL per-block
      f32 scales — and every rank dequantizes ALL contributions (its own
      included, so totals stay bit-identical across ranks) and sums in
      f32.  ``(p-1)`` wire bytes/elem beats the code-sum's ``4(p-1)/p``
      there, and one rendezvous replaces the pmax + reduce pair.
    * ``p >= 4``: per-block amax is shared via ``lax.pmax`` so all ranks
      quantize onto the same grid, then the int8 codes are summed exactly
      in int16 (exact for <= 256 pods: 127 * 256 < 2**15).

    With ``err`` the previous residual is folded in first and the new
    LOCAL residual is returned: ``(total, new_err)``; otherwise just
    ``total``.
    """
    axes = _axes(axes) if axes else ()
    x32 = x.astype(jnp.float32)
    if err is not None:
        x32 = x32 + err.astype(jnp.float32)
    p = _axes_count(axes)
    if p <= 3:
        q, scale, meta = block_quantize(x32, block=block, qmax=Q8_MAX,
                                        stochastic=stochastic, key=key)
        local = block_dequantize(q, scale, meta, x.shape, jnp.float32)
        if axes and p > 1:
            nb = scale.shape[0]
            wire = jnp.concatenate([
                lax.bitcast_convert_type(q, jnp.uint8).reshape(-1),
                lax.bitcast_convert_type(scale, jnp.uint8).reshape(-1)])
            length = wire.shape[0]
            # raw-collective: the fused u8 gather IS the scheme body
            g = scoped(lax.all_gather, wire, axes, axis=0, tiled=True) \
                .reshape(p, length)
            codes = lax.bitcast_convert_type(
                g[:, :length - 4 * nb], jnp.int8).reshape(p, *q.shape)
            scales = lax.bitcast_convert_type(
                g[:, length - 4 * nb:].reshape(p, nb, 4), jnp.float32)
            blocks = (codes.astype(jnp.float32)
                      * scales[:, :, None]).sum(axis=0)
            total = _from_blocks(blocks, meta[0], x.shape, jnp.float32)
        else:
            total = local
        out = total.astype(x.dtype)
        if err is None:
            return out
        return out, (x32 - local)
    q, scale, meta = block_quantize(x32, block=block, qmax=Q8_MAX,
                                    shared_axes=axes, stochastic=stochastic,
                                    key=key)
    local = block_dequantize(q, scale, meta, x.shape, jnp.float32)
    # raw-collective: int16 wire sum IS the scheme body (registry q8_hier)
    tot16 = scoped(lax.psum, q.astype(jnp.int16), axes)
    total = _from_blocks(tot16.astype(jnp.float32) * scale[:, None],
                         meta[0], x.shape, jnp.float32)
    out = total.astype(x.dtype)
    if err is None:
        return out
    return out, (x32 - local)


def qbf16_psum_flat(x: jax.Array, axes, *,
                    err: Optional[jax.Array] = None):
    """bf16-on-the-wire psum of ``x`` over ``axes`` (no scales).

    Scale-free truncation: each contribution is rounded to bf16, crosses
    the wire as a bitcast ``uint16`` gather, and the sum runs locally in
    f32.  The bitcast matters twice: integer collectives lower natively on
    every backend (XLA's CPU bf16 normalization would silently widen a
    bf16 collective to an f32 wire), and the local f32 accumulation keeps
    the error at one rounding per contribution instead of one per ring
    hop.  Exact when ``x`` is already bf16.
    """
    axes = _axes(axes) if axes else ()
    x32 = x.astype(jnp.float32)
    if err is not None:
        x32 = x32 + err.astype(jnp.float32)
    wire = x32.astype(jnp.bfloat16)
    if axes:
        codes = lax.bitcast_convert_type(wire, jnp.uint16)
        # raw-collective: the u16 bridge exchange IS the scheme body
        g = scoped(lax.all_gather, codes, axes, axis=0, tiled=False)
        tot = lax.bitcast_convert_type(g, jnp.bfloat16) \
            .astype(jnp.float32).sum(axis=0)
    else:
        tot = wire.astype(jnp.float32)
    out = tot.astype(x.dtype)
    if err is None:
        return out
    return out, (x32 - wire.astype(jnp.float32))


def _bridge_psum(x, fast_axis, slow_axis, axis, bridge_core, err):
    """Two-tier scaffold shared by the quantized psum bodies.

    Full-precision ``psum_scatter`` over the fast tier, quantized
    ``bridge_core`` over the slow tier, full-precision ``all_gather``
    back.  On a single-tier communicator (``slow_axis=None``) the whole
    reduction IS the bridge — the gradient-bridge case ``reduce_grads``
    dispatches — so the core runs over ``fast_axis`` with no scatter.
    """
    fast = _axes(fast_axis)
    if slow_axis is None:
        return bridge_core(x, fast, err)
    shard = scoped(lax.psum_scatter, x, fast, scatter_dimension=axis,
                   tiled=True)
    res = bridge_core(shard, _axes(slow_axis), err)
    total, new_err = res if err is not None else (res, None)
    out = scoped(lax.all_gather, total, fast, axis=axis, tiled=True)
    if err is None:
        return out
    return out, new_err


def q8_hier_psum(x: jax.Array, *, fast_axis, slow_axis=None, axis: int = 0,
                 block: int = DEFAULT_BLOCK, err=None):
    """Hier allreduce with an int8 bridge: on-node stages full precision."""
    def core(v, axes, e):
        return q8_psum_flat(v, axes, block=block, err=e)
    return _bridge_psum(x, fast_axis, slow_axis, axis, core, err)


def qbf16_hier_psum(x: jax.Array, *, fast_axis, slow_axis=None,
                    axis: int = 0, err=None):
    """Hier allreduce with a bf16 bridge: on-node stages full precision."""
    def core(v, axes, e):
        return qbf16_psum_flat(v, axes, err=e)
    return _bridge_psum(x, fast_axis, slow_axis, axis, core, err)


# ---------------------------------------------------------------------------
# Quantized allgather bodies
# ---------------------------------------------------------------------------

def _bridge_gather_blocks(q_flat, scale, slow_axis):
    """Gather int8 codes + f32 scales across the bridge (untiled)."""
    slow = _axes(slow_axis)
    # raw-collective: the compressed bridge exchange IS the scheme body
    gq = scoped(lax.all_gather, q_flat, slow, axis=0, tiled=False)
    gs = scoped(lax.all_gather, scale, slow, axis=0, tiled=False)
    return gq, gs


def _restore_own_region(out, node, slow_axis, axis):
    """Overwrite this pod's region with the exact full-precision copy —
    a pod never pays quantization error for its own contribution."""
    start = axis_index(slow_axis) * node.shape[axis]
    return lax.dynamic_update_slice_in_dim(
        out, node.astype(out.dtype), start, axis=axis)


def _concat_pods(deq_flat, node_shape, axis, n_pods):
    """(n_pods, flat) -> concatenation of pod regions along ``axis``."""
    per_pod = deq_flat.reshape((n_pods,) + tuple(node_shape))
    return jnp.concatenate([per_pod[i] for i in range(n_pods)], axis=axis)


def q8_hier_all_gather(x: jax.Array, *, fast_axis, slow_axis=None,
                       axis: int = 0, block: int = DEFAULT_BLOCK):
    """Hier allgather with an int8 bridge.

    Intra-pod gather stays full precision (shared-memory tier); the node
    region is per-block quantized with LOCAL scales and both codes and
    scales cross the bridge.  The caller's own pod region is restored
    exactly afterwards.
    """
    fast = _axes(fast_axis)
    node = scoped(lax.all_gather, x, fast, axis=axis, tiled=True)
    if slow_axis is None:
        return node
    q, scale, meta = block_quantize(node, block=block, qmax=Q8_MAX)
    gq, gs = _bridge_gather_blocks(q.reshape(-1), scale, slow_axis)
    n_pods = gq.shape[0]
    blocks = gq.reshape(n_pods, *q.shape).astype(jnp.float32) \
        * gs[:, :, None]
    deq = blocks.reshape(n_pods, -1)[:, :meta[0]]
    out = _concat_pods(deq, node.shape, axis, n_pods).astype(x.dtype)
    return _restore_own_region(out, node, slow_axis, axis)


def qbf16_hier_all_gather(x: jax.Array, *, fast_axis, slow_axis=None,
                          axis: int = 0):
    """Hier allgather with a bf16 bridge (scale-free truncation)."""
    fast = _axes(fast_axis)
    node = scoped(lax.all_gather, x, fast, axis=axis, tiled=True)
    if slow_axis is None:
        return node
    # the wire carries bitcast u16: an integer gather lowers natively
    # everywhere, where a bf16 float collective would be widened to f32 by
    # XLA's CPU bf16 normalization (silently doubling the wire)
    codes = lax.bitcast_convert_type(node.astype(jnp.bfloat16), jnp.uint16)
    # raw-collective: the compressed bridge exchange IS the scheme body
    gw = scoped(lax.all_gather, codes, _axes(slow_axis), axis=axis,
                tiled=True)
    wide = lax.bitcast_convert_type(gw, jnp.bfloat16)
    out = wide.astype(jnp.float32).astype(x.dtype)
    return _restore_own_region(out, node, slow_axis, axis)


def q4_shared_all_gather(x: jax.Array, *, fast_axis, slow_axis=None,
                         axis: int = 0, block: int = DEFAULT_BLOCK):
    """Shared-window allgather with a packed-int4 bridge.

    Mirrors ``shared_all_gather``: the result lives ONCE per pod, sharded
    over ``fast_axis``; only the bridge exchange is compressed (two
    nibbles per byte + per-block f32 scales).  Identity on one pod.
    """
    if slow_axis is None:
        return x
    if x.size % 2:
        raise ValueError(f"q4 shared allgather needs an even payload size, "
                         f"got {x.shape}")
    q, scale, meta = block_quantize(x, block=block, qmax=Q4_MAX)
    packed = pack_int4(q.reshape(-1).reshape(-1, 2)).reshape(-1)
    slow = _axes(slow_axis)
    # raw-collective: the packed-int4 bridge exchange IS the scheme body
    gp = scoped(lax.all_gather, packed, slow, axis=0, tiled=False)
    gs = scoped(lax.all_gather, scale, slow, axis=0, tiled=False)
    n_pods = gp.shape[0]
    codes = unpack_int4(gp).reshape(n_pods, *q.shape).astype(jnp.float32)
    deq = (codes * gs[:, :, None]).reshape(n_pods, -1)[:, :meta[0]]
    out = _concat_pods(deq, x.shape, axis, n_pods).astype(x.dtype)
    return _restore_own_region(out, x, slow_axis, axis)
