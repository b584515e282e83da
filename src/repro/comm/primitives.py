"""Shard_map-body collective primitives for two-tier meshes.

This module is the *implementation* layer of ``repro.comm``: every function
operates on the local shard and takes mesh axis names.  ``fast_axis`` is the
intra-pod tier (ICI — the paper's shared-memory node); ``slow_axis`` is the
cross-pod tier (DCN — the paper's network between nodes).  Each may be a
single name or a tuple of names.

Callers should not use these free functions directly: construct a
``repro.comm.Communicator`` and dispatch through the scheme registry
(``repro.comm.registry``).  (The ``repro.core.collectives`` shims were
removed after their one-release deprecation window.)

Three families, mirroring the paper's comparison (the chunked ``pipelined``
family lives in ``repro.comm.pipeline``):

* ``naive_*``   — pure-MPI analogue: single flat phase, result fully
                  replicated on every chip (one private copy per rank).
* ``hier_*``    — two-phase (intra-pod, then bridge) schedule producing the
                  same fully-replicated result; isolates the *latency* effect
                  of the hierarchical schedule (paper Figs 7-10).
* ``shared_*``  — the paper's memory-optimal scheme: the result exists ONCE
                  per pod, sharded over ``fast_axis`` (the shared-memory
                  window).  Children "load" from it with ``shared_read`` (an
                  intra-pod gather at use time — the TPU's load/store).

The multi-leader refinement (paper ref [14]) is built in: chip *i* of every
pod is the leader for shard *i*, so the bridge exchange is spread over all
chips instead of serialized through one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.substrate.compat import axis_size as _axis_size_one


def _axes(ax) -> tuple:
    return tuple(ax) if isinstance(ax, (tuple, list)) else (ax,)


def scoped(op, x, axes, *args, **kwargs):
    """Run the ``lax`` collective ``op`` over the mesh ``axes`` inside the
    named scope ``comm.<op>[<axes>]``, the axis names in call order
    (``comm.all_gather[data]``, ``comm.psum[pod,data]``), so that a profile
    can tell the on-node stage from the bridge.  The scope labels the
    compiled instructions' metadata only; ``axes`` reaches ``op`` as given.
    Every collective of ``repro.comm``, ``core.sync`` and ``repro.models``
    goes through here (``scripts/check_api_surface.py``)."""
    names = ",".join(str(a) for a in _axes(axes))
    with jax.named_scope(f"comm.{op.__name__}[{names}]"):
        return op(x, axes, *args, **kwargs)


def axis_size(ax) -> int:
    s = 1
    for a in _axes(ax):
        s *= _axis_size_one(a)
    return s


def axis_index(ax) -> jax.Array:
    """Linearized index over (possibly tuple) axis, row-major."""
    axes = _axes(ax)
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * _axis_size_one(a) + lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# Allgather (paper §4.1)
# ---------------------------------------------------------------------------

def naive_all_gather(x: jax.Array, *, fast_axis, slow_axis=None,
                     axis: int = 0) -> jax.Array:
    """Pure-MPI analogue: one flat all-gather; full private copy per chip."""
    names = (_axes(slow_axis) if slow_axis else ()) + _axes(fast_axis)
    return scoped(lax.all_gather, x, names, axis=axis, tiled=True)


def hier_all_gather(x: jax.Array, *, fast_axis, slow_axis=None,
                    axis: int = 0) -> jax.Array:
    """Two-phase allgather: intra-pod gather, then bridge exchange of whole
    node regions (leaders' ``MPI_Allgatherv`` in the regular case)."""
    node_region = scoped(lax.all_gather, x, _axes(fast_axis), axis=axis,
                         tiled=True)
    if slow_axis is None:
        return node_region
    return scoped(lax.all_gather, node_region, _axes(slow_axis), axis=axis,
                  tiled=True)


def shared_all_gather(x: jax.Array, *, fast_axis, slow_axis=None,
                      axis: int = 0) -> jax.Array:
    """Paper's scheme: children write their partitions in place (no intra-pod
    copies); only the bridge exchange runs.  Chip *i* ends holding shard *i*
    of the pod's single shared copy: the concatenation over pods of every
    pod's chip-*i* contribution.

    Global element order of the shared copy is (local_rank, pod) — i.e. the
    node-sorted rank array of paper §6 with the multi-leader interleave.  Use
    ``shared_read`` to materialize the full buffer (ordered (local, pod)), or
    ``shared_to_rank_order`` to get SMP rank order.
    """
    if slow_axis is None:
        return x  # single node: partition already in the shared window
    return scoped(lax.all_gather, x, _axes(slow_axis), axis=axis, tiled=True)


def shared_read(shard: jax.Array, *, fast_axis, axis: int = 0) -> jax.Array:
    """Load the pod-shared buffer (an intra-pod gather at use time)."""
    return scoped(lax.all_gather, shard, _axes(fast_axis), axis=axis,
                  tiled=True)


def shared_to_rank_order(full: jax.Array, *, num_pods: int,
                         chips_per_pod: int, axis: int = 0) -> jax.Array:
    """Reorder a ``shared_read`` result from (local, pod, chunk) layout to
    SMP rank order (pod, local, chunk) along ``axis``."""
    moved = jnp.moveaxis(full, axis, 0)
    n = moved.shape[0]
    chunk = n // (num_pods * chips_per_pod)
    r = moved.reshape((chips_per_pod, num_pods, chunk) + moved.shape[1:])
    r = jnp.swapaxes(r, 0, 1)
    r = r.reshape((n,) + moved.shape[1:])
    return jnp.moveaxis(r, 0, axis)


def shared_all_gather_v(x_padded: jax.Array, valid: jax.Array, *,
                        slow_axis=None, axis: int = 0
                        ) -> tuple[jax.Array, jax.Array]:
    """Irregular variant (paper Figs 4/10): per-chip contributions of
    different true lengths, padded to a common max.  Returns the bridge-
    gathered padded blocks plus the gathered valid-counts; the compaction map
    is ``plans.GatherPlan`` (a one-off, like the paper's counts/displs).

    On a single node (``slow_axis=None``) there is no bridge: the local
    partition is already in the shared window, so the "gathered" leading pod
    dimension has extent 1."""
    if slow_axis is None:
        return jnp.expand_dims(x_padded, axis), valid[None]
    blocks = scoped(lax.all_gather, x_padded, _axes(slow_axis), axis=axis,
                    tiled=False)
    counts = scoped(lax.all_gather, valid, _axes(slow_axis), tiled=False)
    return blocks, counts


# ---------------------------------------------------------------------------
# Broadcast (paper §4.2)
# ---------------------------------------------------------------------------

def naive_broadcast(x: jax.Array, *, root: int, fast_axis, slow_axis=None
                    ) -> jax.Array:
    """Pure-MPI analogue: every chip ends with a private full copy."""
    names = (_axes(slow_axis) if slow_axis else ()) + _axes(fast_axis)
    me = axis_index(names)
    contrib = jnp.where(me == root, x, jnp.zeros_like(x))
    return scoped(lax.psum, contrib, names)


def _flat_root(root, fast_axis, slow_axis):
    """Resolve the (root_pod, root_local) pair from a flat SMP rank.

    ``root`` is a flat rank in (pod, chip) row-major order — the same
    numbering as ``naive_broadcast``.  (The legacy ``root_pod=`` pod-only
    spelling was removed after its deprecation release; pass
    ``root=pod * ranks_per_node`` for a pod's leader.)
    """
    if root is None:
        root = 0
    c = axis_size(fast_axis)
    if isinstance(root, int) and isinstance(c, int):
        total = c * (axis_size(slow_axis) if slow_axis is not None else 1)
        if isinstance(total, int) and not 0 <= root < total:
            raise ValueError(f"root={root} out of range for "
                             f"{total} ranks")
    return root // c, root % c


def hier_broadcast(x: jax.Array, *, root: int | None = None, fast_axis,
                   slow_axis=None) -> jax.Array:
    """Two-phase broadcast to full replication: bridge bcast between leaders,
    then intra-pod bcast (leader -> children copies of the naive scheme).

    ``root`` is the flat SMP rank of the source (same numbering as
    ``naive_broadcast``); the chip holding it acts as its pod's leader."""
    my_pod_root, my_local_root = _flat_root(root, fast_axis, slow_axis)
    fast = _axes(fast_axis)
    me_fast = axis_index(fast)
    if slow_axis is not None:
        slow = _axes(slow_axis)
        my_pod = axis_index(slow)
        lead = jnp.where((my_pod == my_pod_root) & (me_fast == my_local_root),
                         x, jnp.zeros_like(x))
        # bridge bcast (only leaders nonzero)
        lead = scoped(lax.psum, lead, slow)
    else:
        lead = jnp.where(me_fast == my_local_root, x, jnp.zeros_like(x))
    lead = jnp.where(me_fast == my_local_root, lead, jnp.zeros_like(lead))
    return scoped(lax.psum, lead, fast)


def shared_broadcast(x: jax.Array, *, root: int | None = None, fast_axis,
                     slow_axis=None, axis: int = 0) -> jax.Array:
    """Paper's scheme: ONE shared copy per pod, sharded over ``fast_axis``.

    Phase 1 (intra-pod scatter at the root pod): the root chip's message is
    reduce-scattered so chip *i* holds shard *i* — this is the write into the
    shared window.  Phase 2 (bridge): shard *i* crosses pods once (multi-
    leader bcast).  Children read via ``shared_read``.

    ``root`` is the flat SMP rank of the source (same numbering as
    ``naive_broadcast``).
    """
    my_pod_root, my_local_root = _flat_root(root, fast_axis, slow_axis)
    fast = _axes(fast_axis)
    me_fast = axis_index(fast)
    contrib = jnp.where(me_fast == my_local_root, x, jnp.zeros_like(x))
    shard = scoped(lax.psum_scatter, contrib, fast, scatter_dimension=axis,
                   tiled=True)
    if slow_axis is None:
        return shard
    slow = _axes(slow_axis)
    my_pod = axis_index(slow)
    shard = jnp.where(my_pod == my_pod_root, shard, jnp.zeros_like(shard))
    return scoped(lax.psum, shard, slow)


# ---------------------------------------------------------------------------
# Allreduce / reductions (gradient bridge — paper's scheme applied to psum)
# ---------------------------------------------------------------------------

def naive_psum(x: jax.Array, *, fast_axis, slow_axis=None) -> jax.Array:
    """Flat allreduce; result replicated per chip."""
    names = (_axes(slow_axis) if slow_axis else ()) + _axes(fast_axis)
    return scoped(lax.psum, x, names)


def hier_psum(x: jax.Array, *, fast_axis, slow_axis=None, axis: int = 0
              ) -> jax.Array:
    """Two-phase allreduce to full replication: intra-pod reduce-scatter,
    bridge allreduce on shards (multi-leader), intra-pod allgather."""
    shard = scoped(lax.psum_scatter, x, _axes(fast_axis),
                   scatter_dimension=axis, tiled=True)
    if slow_axis is not None:
        shard = scoped(lax.psum, shard, _axes(slow_axis))
    return scoped(lax.all_gather, shard, _axes(fast_axis), axis=axis,
                  tiled=True)


def shared_psum_scatter(x: jax.Array, *, fast_axis, slow_axis=None,
                        axis: int = 0) -> jax.Array:
    """Paper's memory-optimal reduction: result exists once per pod, sharded
    over ``fast_axis``.  This is the gradient-reduction of hier train mode:
    children write partial sums (intra-pod RS), leaders exchange on the
    bridge, the reduced value never gets replicated."""
    shard = scoped(lax.psum_scatter, x, _axes(fast_axis),
                   scatter_dimension=axis, tiled=True)
    if slow_axis is not None:
        shard = scoped(lax.psum, shard, _axes(slow_axis))
    return shard


def naive_reduce_scatter(x: jax.Array, *, fast_axis, slow_axis=None,
                         axis: int = 0) -> jax.Array:
    """Flat MPI_Reduce_scatter analogue: every rank ends with its 1/R slice
    of the global sum, rank-major (pod, chip) order."""
    names = (_axes(slow_axis) if slow_axis else ()) + _axes(fast_axis)
    return scoped(lax.psum_scatter, x, names, scatter_dimension=axis,
                  tiled=True)


# ---------------------------------------------------------------------------
# All-to-all (MoE dispatch / SUMMA panel exchange / transpose workloads)
# ---------------------------------------------------------------------------

def naive_all_to_all(x: jax.Array, *, fast_axis, slow_axis=None,
                     axis: int = 0) -> jax.Array:
    """Pure-MPI analogue: one flat all-to-all over every rank.  The local
    buffer along ``axis`` is R equal chunks in flat (pod, chip) rank order;
    chunk *s* goes to rank *s* and the result is ordered by source rank."""
    names = (_axes(slow_axis) if slow_axis else ()) + _axes(fast_axis)
    return scoped(lax.all_to_all, x, names, split_axis=axis, concat_axis=axis,
                  tiled=True)


def hier_all_to_all(x: jax.Array, *, fast_axis, slow_axis=None,
                    axis: int = 0) -> jax.Array:
    """Node-aware two-phase all-to-all (same result as ``naive_all_to_all``).

    Phase 1 (bridge): whole node-sized superchunks cross pods once — the
    leaders' aggregated exchange, P messages instead of P*c.  Phase 2
    (intra-pod): ranks redistribute within the shared-memory node, one
    untiled exchange per fast-tier axis.  Rank order of the result is
    identical to the flat scheme.
    """
    fast = _axes(fast_axis)
    if slow_axis is not None:
        x = scoped(lax.all_to_all, x, _axes(slow_axis), split_axis=axis,
                   concat_axis=axis, tiled=True)
    pods = axis_size(slow_axis) if slow_axis is not None else 1
    fast_sizes = tuple(_axis_size_one(a) for a in fast)
    chips = 1
    for s in fast_sizes:
        chips *= s
    moved = jnp.moveaxis(x, axis, 0)
    n = moved.shape[0]
    if n % (pods * chips):
        raise ValueError(f"all-to-all buffer dim {n} must tile over "
                         f"{pods * chips} ranks")
    chunk = n // (pods * chips)
    y = moved.reshape((pods,) + fast_sizes + (chunk,) + moved.shape[1:])
    for i, a in enumerate(fast):
        if fast_sizes[i] > 1:
            y = scoped(lax.all_to_all, y, a, split_axis=1 + i,
                       concat_axis=1 + i, tiled=False)
    y = y.reshape((n,) + moved.shape[1:])
    return jnp.moveaxis(y, 0, axis)
