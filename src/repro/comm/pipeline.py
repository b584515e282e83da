"""Pipelined (chunked two-phase) collectives + fused collective-matmul.

The plain ``hier`` schedule serializes the bridge (slow-axis) stage behind
the on-node (fast-axis) stage: no byte crosses pods until the whole node
region is assembled.  The paper's companion study (Zhou et al.,
arXiv:2007.11496) closes that gap by *segmenting* the message: split it
into ``n_chunks`` pieces and software-pipeline the bridge stage of chunk
*k* against the on-node stage of chunk *k+1*.

Every primitive here produces bit-identical results to its unchunked
``naive``/``hier`` counterpart (the chunk split/merge is pure local layout
algebra) and moves exactly the same total link bytes — chunking only
re-schedules them, which is why the ``pipelined`` registry entry reuses the
``hier`` closed forms.  The latency win is modeled by
``core.plans.pipelined_time_model`` and *measured* by the bench autotune
sweep (``n_chunks`` is a registry tunable).

Integrity discipline: each chunk's staged intermediate lives in one of TWO
alternating ``SharedWindow`` epochs (double buffering, the paper's §6 rule
applied per segment).  A chunk's store into buffer *b* is ordered after the
previous occupant of *b* was fully consumed (``fence_local`` — an
``optimization_barrier`` dependency, zero wire bytes), so the pipeline
never holds more than two segments in flight and a read of a still-dirty
buffer raises ``WindowEpochError`` instead of serving torn data.

The fused ``ag_matmul`` / ``matmul_rs`` primitives apply the same chunking
to compute overlap: per-chunk gather/scatter interleaved with the panel
matmul (``repro.kernels`` Pallas kernel or ``jnp.matmul``), double-buffered
the same way.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm import primitives as p
from repro.comm.window import SharedWindow

DEFAULT_CHUNKS = 2


# ---------------------------------------------------------------------------
# Chunk layout algebra (pure local reshapes — zero wire bytes)
# ---------------------------------------------------------------------------

def _split_blocked(x: jax.Array, axis: int, n_chunks: int) -> list[jax.Array]:
    """Contiguous split of ``x`` along ``axis`` into ``n_chunks`` pieces."""
    n = x.shape[axis]
    if n_chunks < 1 or n % n_chunks:
        raise ValueError(f"cannot split dim {n} into n_chunks={n_chunks}")
    return jnp.split(x, n_chunks, axis=axis)


def _split_strided(x: jax.Array, axis: int, n_chunks: int, blocks: int
                   ) -> list[jax.Array]:
    """Strided split: view ``axis`` as (blocks, n_chunks, piece); chunk *j*
    is every block's *j*-th piece (the reduce-scatter pre-interleave)."""
    moved = jnp.moveaxis(x, axis, 0)
    n = moved.shape[0]
    if n_chunks < 1 or n % (blocks * n_chunks):
        raise ValueError(f"cannot stride dim {n} over blocks={blocks} x "
                         f"n_chunks={n_chunks}")
    piece = n // (blocks * n_chunks)
    r = moved.reshape((blocks, n_chunks, piece) + moved.shape[1:])
    return [jnp.moveaxis(r[:, j].reshape((blocks * piece,) + moved.shape[1:]),
                         0, axis) for j in range(n_chunks)]


def _merge_strided(parts: list[jax.Array], axis: int, blocks: int
                   ) -> jax.Array:
    """Inverse of ``_split_strided``: part *j* holds every block's *j*-th
    piece; the merge restores block-major (e.g. rank-major) element order."""
    moved = [jnp.moveaxis(q, axis, 0) for q in parts]
    nc = len(moved)
    if nc == 1:
        return parts[0]
    piece = moved[0].shape[0] // blocks
    rest = moved[0].shape[1:]
    r = jnp.stack([m.reshape((blocks, piece) + rest) for m in moved], axis=1)
    return jnp.moveaxis(r.reshape((blocks * nc * piece,) + rest), 0, axis)


def _merge_blocked(parts: list[jax.Array], axis: int) -> jax.Array:
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


# ---------------------------------------------------------------------------
# The double-buffered two-phase pipeline driver
# ---------------------------------------------------------------------------

def _token_after(x) -> jax.Array:
    """A scalar token data-dependent on ``x`` (optimization_barrier joins
    the tuple, never arithmetic on the payload)."""
    _, tok = lax.optimization_barrier((x, jnp.ones((), jnp.float32)))
    return tok


def _node_comm(fast_axis) -> SimpleNamespace:
    """Minimal node-communicator view for a staged ``SharedWindow`` (a real
    ``Communicator`` would be an import cycle: registry -> pipeline)."""
    return SimpleNamespace(fast_axis=fast_axis, slow_axis=None,
                           pods=None, chips=None)


def two_phase_pipeline(chunks: list[jax.Array], *, stage_a: Callable,
                       stage_b: Callable, fast_axis, axis: int
                       ) -> list[jax.Array]:
    """Run ``stage_b(stage_a(chunk))`` per chunk with double-buffered window
    epochs between the stages.

    ``stage_a`` of chunk *k* and ``stage_b`` of chunk *k-1* share no data
    dependency, so the compiler is free to overlap them (the software
    pipeline).  The only added ordering is the two-buffer reuse rule: the
    epoch of chunk *k* (buffer ``k % 2``) opens after chunk *k-2*'s stage_b
    consumed that buffer.  That ordering is ``optimization_barrier``-
    threaded — zero wire bytes, values bit-preserved — and is emitted ONLY
    where the constraint binds (``k >= 2``): a fresh buffer's epoch closes
    by dataflow alone, so ``n_chunks <= 2`` lowers with no barriers at all
    and ``n_chunks == 1`` is bit- and schedule-identical to the unchunked
    two-phase path.
    """
    import dataclasses as _dc

    comm = _node_comm(fast_axis)
    n = len(chunks)
    free: list[Optional[jax.Array]] = [None, None]
    outs = []
    for k, ck in enumerate(chunks):
        b = k % 2
        staged = stage_a(ck)
        win = SharedWindow(comm, staged, axis=axis, epoch=k, dirty=True)
        if free[b] is not None:
            # buffer b reusable only once its previous occupant was consumed
            win = win.fence_local(free[b])
        else:
            # fresh buffer: XLA dataflow already orders store before read —
            # close the epoch with bookkeeping only (no barrier, no copy)
            win = _dc.replace(win, dirty=False, epoch=k + 1)
        out = stage_b(win.shard)
        if k + 2 < n:                 # someone will reuse this buffer
            free[b] = _token_after(out)
        outs.append(out)
    return outs


# ---------------------------------------------------------------------------
# Pipelined collective primitives (bit-identical to the hier/naive results)
# ---------------------------------------------------------------------------

def pipelined_all_gather(x: jax.Array, *, fast_axis, slow_axis=None,
                         axis: int = 0, n_chunks: int = DEFAULT_CHUNKS
                         ) -> jax.Array:
    """Chunked two-phase allgather == ``hier_all_gather`` bit-for-bit.

    Per chunk: intra-pod gather (stage a), bridge exchange of the node
    region (stage b).  The merge interleaves per-chunk rank-major results
    back into the unchunked rank-major order.
    """
    chunks = _split_blocked(x, axis, n_chunks)
    ranks = p.axis_size(fast_axis) * (p.axis_size(slow_axis)
                                      if slow_axis is not None else 1)

    def stage_a(ck):
        return p.scoped(lax.all_gather, ck, p._axes(fast_axis), axis=axis,
                        tiled=True)

    def stage_b(region):
        if slow_axis is None:
            return region
        return p.scoped(lax.all_gather, region, p._axes(slow_axis), axis=axis,
                        tiled=True)

    outs = two_phase_pipeline(chunks, stage_a=stage_a, stage_b=stage_b,
                              fast_axis=fast_axis, axis=axis)
    return _merge_strided(outs, axis, blocks=ranks)


def pipelined_broadcast(x: jax.Array, *, root: int = 0, fast_axis,
                        slow_axis=None, axis: int = 0,
                        n_chunks: int = DEFAULT_CHUNKS) -> jax.Array:
    """Chunked two-phase broadcast == ``hier_broadcast`` bit-for-bit.

    Per chunk: bridge bcast between the pods' leader chips (stage a), then
    the intra-pod leader->children copy (stage b) — so the on-node fan-out
    of chunk *k-1* overlaps the bridge crossing of chunk *k*.
    """
    my_pod_root, my_local_root = p._flat_root(root, fast_axis, slow_axis)
    fast = p._axes(fast_axis)
    me_fast = p.axis_index(fast)

    def stage_a(ck):
        if slow_axis is None:
            return jnp.where(me_fast == my_local_root, ck,
                             jnp.zeros_like(ck))
        slow = p._axes(slow_axis)
        my_pod = p.axis_index(slow)
        lead = jnp.where((my_pod == my_pod_root)
                         & (me_fast == my_local_root), ck,
                         jnp.zeros_like(ck))
        # bridge bcast (leaders nonzero)
        return p.scoped(lax.psum, lead, slow)

    def stage_b(lead):
        lead = jnp.where(me_fast == my_local_root, lead, jnp.zeros_like(lead))
        return p.scoped(lax.psum, lead, fast)

    outs = two_phase_pipeline(_split_blocked(x, axis, n_chunks),
                              stage_a=stage_a, stage_b=stage_b,
                              fast_axis=fast_axis, axis=axis)
    return _merge_blocked(outs, axis)


def pipelined_psum(x: jax.Array, *, fast_axis, slow_axis=None, axis: int = 0,
                   n_chunks: int = DEFAULT_CHUNKS) -> jax.Array:
    """Chunked two-phase allreduce == ``hier_psum`` bit-for-bit.

    Per chunk: intra-pod reduce-scatter (stage a — the window store), then
    bridge allreduce on shards + intra-pod allgather (stage b).
    """
    def stage_a(ck):
        return p.scoped(lax.psum_scatter, ck, p._axes(fast_axis),
                        scatter_dimension=axis, tiled=True)

    def stage_b(shard):
        if slow_axis is not None:
            shard = p.scoped(lax.psum, shard, p._axes(slow_axis))
        return p.scoped(lax.all_gather, shard, p._axes(fast_axis), axis=axis,
                        tiled=True)

    outs = two_phase_pipeline(_split_blocked(x, axis, n_chunks),
                              stage_a=stage_a, stage_b=stage_b,
                              fast_axis=fast_axis, axis=axis)
    return _merge_blocked(outs, axis)


def pipelined_reduce_scatter(x: jax.Array, *, fast_axis, slow_axis=None,
                             axis: int = 0, n_chunks: int = DEFAULT_CHUNKS
                             ) -> jax.Array:
    """Chunked two-phase reduce-scatter: rank *r* ends with the same flat
    1/R slice (rank-major) as ``naive_reduce_scatter``.

    Per chunk: bridge reduce-scatter over pods (stage a), intra-pod
    reduce-scatter of the pod slice (stage b).  The strided pre-split makes
    each chunk carry every rank-slice's *j*-th piece, so the blocked merge
    of per-chunk results is the contiguous unchunked slice.  Unlike the
    other families (whose per-chunk op sequence IS the reference's), the
    two-phase sum reassociates the flat ring's float adds (pods first,
    then chips) — numerically equivalent, not bitwise.
    """
    ranks = p.axis_size(fast_axis) * (p.axis_size(slow_axis)
                                      if slow_axis is not None else 1)
    chunks = _split_strided(x, axis, n_chunks, blocks=ranks)

    def stage_a(ck):
        if slow_axis is None:
            return ck
        return p.scoped(lax.psum_scatter, ck, p._axes(slow_axis),
                        scatter_dimension=axis, tiled=True)

    def stage_b(pod_slice):
        return p.scoped(lax.psum_scatter, pod_slice, p._axes(fast_axis),
                        scatter_dimension=axis, tiled=True)

    outs = two_phase_pipeline(chunks, stage_a=stage_a, stage_b=stage_b,
                              fast_axis=fast_axis, axis=axis)
    return _merge_blocked(outs, axis)


# ---------------------------------------------------------------------------
# Fused collective-matmul (compute overlap)
# ---------------------------------------------------------------------------

def _default_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(a, b)


def _kernel_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    from repro.kernels.ops import matmul as pallas_mm
    lead = a.shape[:-1]
    out = pallas_mm(a.reshape(-1, a.shape[-1]), b)
    return out.reshape(lead + (b.shape[-1],))


def _resolve_mm(use_kernel: bool, matmul: Optional[Callable]) -> Callable:
    if matmul is not None:
        return matmul
    return _kernel_matmul if use_kernel else _default_matmul


class _ReuseFence:
    """The double-buffer reuse discipline of the fused matmul loops, in ONE
    place: ``enter`` orders chunk *j*'s input after buffer ``j % 2``'s
    previous tenant was consumed; ``exit`` records the consumption token —
    only when a later chunk will actually reuse the buffer, so shallow
    pipelines (``n_chunks <= 2``) emit no barriers at all.  (The collective
    pipeline's window-epoch flavor of the same rule lives in
    ``two_phase_pipeline``.)"""

    def __init__(self, n_chunks: int):
        self.n = n_chunks
        self.free: list[Optional[jax.Array]] = [None, None]

    def enter(self, j: int, x: jax.Array) -> jax.Array:
        if self.free[j % 2] is not None:
            x, _ = lax.optimization_barrier((x, self.free[j % 2]))
        return x

    def exit(self, j: int, out: jax.Array) -> jax.Array:
        if j + 2 < self.n:
            self.free[j % 2] = _token_after(out)
        return out


def ag_matmul(x: jax.Array, w_shard: jax.Array, *, fast_axis,
              n_chunks: int = DEFAULT_CHUNKS, use_kernel: bool = False,
              matmul: Optional[Callable] = None) -> jax.Array:
    """``x @ all_gather(w_shard, axis=0)`` — the FSDP window *read* fused
    into the matmul.

    ``w_shard``: this rank's ``(K/c, N)`` shard of the ``(K, N)`` weight,
    sharded over ``fast_axis`` along the contraction dim.  Each chunk
    gathers a strided K-panel of the weight, multiplies the matching
    ``x`` columns and accumulates in fp32 — the gather of panel *k+1* has
    no dependency on the matmul of panel *k* (double-buffered), so the
    window read streams behind the MXU instead of completing up front.

    ``use_kernel=True`` routes panels through the Pallas blocked kernel
    (``repro.kernels.ops.matmul``); default is the jnp matmul (the Pallas
    interpreter is the CPU validation mode, far too slow for benching).
    """
    mm = _resolve_mm(use_kernel, matmul)
    c = p.axis_size(fast_axis)
    s, n_out = w_shard.shape
    if s % n_chunks:
        raise ValueError(f"weight shard rows {s} must divide by "
                         f"n_chunks={n_chunks}")
    k_total = c * s
    if x.shape[-1] != k_total:
        raise ValueError(f"x contraction dim {x.shape[-1]} != gathered "
                         f"weight rows {k_total}")
    piece = s // n_chunks
    lead = x.shape[:-1]
    xr = x.reshape(lead + (c, n_chunks, piece))
    fence = _ReuseFence(n_chunks)
    acc = jnp.zeros(lead + (n_out,), jnp.float32)
    for j in range(n_chunks):
        shard_piece = fence.enter(j, lax.slice_in_dim(
            w_shard, j * piece, (j + 1) * piece, axis=0))
        panel = p.scoped(lax.all_gather, shard_piece, p._axes(fast_axis),
                         axis=0, tiled=True)              # (c*piece, N)
        xj = xr[..., :, j, :].reshape(lead + (c * piece,))
        prod = fence.exit(j, mm(xj, panel))
        acc = acc + prod.astype(jnp.float32)
    return acc.astype(x.dtype)


def ag_matmul_q4(x: jax.Array, w_shard: jax.Array, *, fast_axis,
                 n_chunks: int = DEFAULT_CHUNKS, group: int = 32,
                 use_kernel: bool = False) -> jax.Array:
    """``ag_matmul`` with a packed-int4 weight wire format.

    Each chunk's local K-panel piece is groupwise int4-quantized
    (``quantize_q4``) BEFORE the gather, so the collective moves two
    nibbles per weight plus one f32 scale per ``group`` rows instead of
    four bytes per weight.  The gathered panel is never densified when
    ``use_kernel=True``: the Pallas kernel (``kernels.quant``) unpacks and
    rescales tiles inside the matmul loop.  The per-chip piece must divide
    by ``group`` so concatenated packings respect group boundaries.
    """
    from repro.comm import quantize as qz
    c = p.axis_size(fast_axis)
    s, n_out = w_shard.shape
    if s % n_chunks:
        raise ValueError(f"weight shard rows {s} must divide by "
                         f"n_chunks={n_chunks}")
    piece = s // n_chunks
    if piece % group:
        raise ValueError(f"per-chunk shard rows {piece} must divide by "
                         f"group={group}")
    k_total = c * s
    if x.shape[-1] != k_total:
        raise ValueError(f"x contraction dim {x.shape[-1]} != gathered "
                         f"weight rows {k_total}")
    lead = x.shape[:-1]
    xr = x.reshape(lead + (c, n_chunks, piece))
    fence = _ReuseFence(n_chunks)
    acc = jnp.zeros(lead + (n_out,), jnp.float32)
    for j in range(n_chunks):
        shard_piece = fence.enter(j, lax.slice_in_dim(
            w_shard, j * piece, (j + 1) * piece, axis=0))
        packed, scales = qz.quantize_q4(shard_piece, group=group)
        # raw-collective: the packed-int4 panel gather IS the wire format
        gp = p.scoped(lax.all_gather, packed, p._axes(fast_axis), axis=0,
                      tiled=True)
        gs = p.scoped(lax.all_gather, scales, p._axes(fast_axis), axis=0,
                      tiled=True)
        xj = xr[..., :, j, :].reshape(lead + (c * piece,))
        x2d = xj.reshape(-1, c * piece)
        if use_kernel:
            from repro.kernels.ops import q4_matmul
            prod2d = q4_matmul(x2d, gp, gs, group=group)
        else:
            prod2d = jnp.matmul(
                x2d, qz.dequantize_q4(gp, gs, group=group))
        prod = fence.exit(j, prod2d.reshape(lead + (n_out,)))
        acc = acc + prod.astype(jnp.float32)
    return acc.astype(x.dtype)


def ag_matmul_rows(a_shard: jax.Array, b: jax.Array, *, fast_axis,
                   n_chunks: int = DEFAULT_CHUNKS, use_kernel: bool = False,
                   matmul: Optional[Callable] = None) -> jax.Array:
    """``all_gather(a_shard, axis=0) @ b`` — the row-panel flavor: the
    gathered operand carries OUTPUT rows (e.g. the SUMMA A-panel shared
    window), so chunks produce disjoint row panels — no accumulation; the
    strided merge restores rank-major row order.  The gather of panel *k+1*
    overlaps the matmul of panel *k* (double-buffered)."""
    mm = _resolve_mm(use_kernel, matmul)
    c = p.axis_size(fast_axis)
    rows = a_shard.shape[0]
    if rows % n_chunks:
        raise ValueError(f"shard rows {rows} must divide by "
                         f"n_chunks={n_chunks}")
    piece = rows // n_chunks
    fence = _ReuseFence(n_chunks)
    outs = []
    for j in range(n_chunks):
        pj = fence.enter(j, lax.slice_in_dim(a_shard, j * piece,
                                             (j + 1) * piece, axis=0))
        panel = p.scoped(lax.all_gather, pj, p._axes(fast_axis), axis=0,
                         tiled=True)
        outs.append(fence.exit(j, mm(panel, b)))
    return _merge_strided(outs, 0, blocks=c)


def matmul_rs(x: jax.Array, w: jax.Array, *, axis_name, scatter_dim: int = 0,
              n_chunks: int = DEFAULT_CHUNKS, use_kernel: bool = False,
              matmul: Optional[Callable] = None) -> jax.Array:
    """``reduce_scatter(x @ w)`` over ``axis_name`` along ``scatter_dim`` —
    the partial-sum *store* fused into the matmul.

    Output rows are computed in ``n_chunks`` strided panels; the
    reduce-scatter of panel *k* overlaps the matmul of panel *k+1*.  The
    strided split mirrors ``pipelined_reduce_scatter``: the blocked merge of
    scattered panels is exactly the contiguous unchunked shard.
    """
    mm = _resolve_mm(use_kernel, matmul)
    n = p.axis_size(axis_name)
    chunks = _split_strided(x, scatter_dim, n_chunks, blocks=n)
    fence = _ReuseFence(n_chunks)
    outs = []
    for j, xc in enumerate(chunks):
        prod = mm(fence.enter(j, xc), w)
        out = p.scoped(lax.psum_scatter, prod, p._axes(axis_name),
                       scatter_dimension=scatter_dim, tiled=True)
        outs.append(fence.exit(j, out))
    return _merge_blocked(outs, scatter_dim)
