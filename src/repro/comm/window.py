"""SharedWindow: the MPI-3 shared-memory window as a first-class object.

In the paper, replicated data lives once per node in an
``MPI_Win_allocate_shared`` segment; on-node ranks load/store it directly,
and integrity is guarded by *synchronization epochs*: stores made in one
epoch become readable only after the epoch is closed (``MPI_Win_fence`` /
the two-barrier discipline of §6).

Here the window is the pod-sharded buffer the ``shared`` scheme produces:
chip *i* physically holds shard *i* of the node's single logical copy.
``SharedWindow`` wraps that shard together with its communicator and an
explicit epoch counter:

* ``read()``            — load the full node buffer (intra-pod gather at use
                          time; AD transpose is the reduce-scatter store);
* ``store(x)``          — replace the local shard, opening a *dirty* store
                          epoch;
* ``accumulate(x)``     — reduce-scatter partial contributions into the
                          window (the gradient store), also dirty;
* ``fence()``           — close the epoch: a ``core.sync`` barrier over the
                          node makes every rank's result data-dependent on
                          every other rank's stores, then marks the window
                          clean and bumps ``epoch``.

Reading a dirty window raises — that is the paper's data-integrity rule
("a process cannot read until all writers finished") made unskippable.

Inside one jitted step XLA's dataflow already orders exchange before use;
the fence exists for *cross-step* control sync and to make the epoch
discipline explicit and testable.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm import primitives as p


class WindowEpochError(RuntimeError):
    """A read hit an open (dirty) store epoch — call ``fence()`` first."""


@dataclasses.dataclass(frozen=True)
class SharedWindow:
    """One node-shared buffer: the local shard + its epoch state.

    ``comm`` is the ``repro.comm.Communicator`` whose fast tier is the node
    (the ``sharedmemComm`` of ``MPI_Comm_split_type``); ``axis`` is the
    array dimension the buffer is sharded over.
    """

    comm: object                      # Communicator (typed loosely: no cycle)
    shard: jax.Array
    axis: int = 0
    epoch: int = 0
    dirty: bool = False

    # -- stores (open an epoch) ----------------------------------------------
    def store(self, shard: jax.Array) -> "SharedWindow":
        """Replace this rank's partition (a direct store into the segment).
        The window is dirty until the next ``fence()``."""
        return dataclasses.replace(self, shard=shard, dirty=True)

    def accumulate(self, x: jax.Array) -> "SharedWindow":
        """Reduce partial contributions from every on-node rank into the
        window shards (intra-pod reduce-scatter — the gradient store)."""
        shard = p.scoped(lax.psum_scatter, x, p._axes(self.comm.fast_axis),
                         scatter_dimension=self.axis, tiled=True)
        return dataclasses.replace(self, shard=shard, dirty=True)

    # -- synchronization ------------------------------------------------------
    def fence(self) -> "SharedWindow":
        """Close the current epoch (``MPI_Win_fence`` on the node comm).

        Built on ``core.sync.barrier``: the returned shard is data-dependent
        on every on-node rank's shard, so no consumer of the fenced window
        can be scheduled before every store of the closing epoch.

        The dependency is threaded with ``optimization_barrier``, never
        arithmetic on the payload — the fence is exactly value-preserving
        even for NaN/inf shards (a near-overflow gradient must not be
        corrupted by its own synchronization) and for zero-size shards."""
        from repro.core import sync
        # token computable only after this rank's stores...
        shard, token = lax.optimization_barrier(
            (self.shard, jnp.ones((), jnp.float32)))
        done = sync.barrier(token, self.comm.fast_axis)
        # ...and the fenced shard available only after every rank reported.
        shard, _ = lax.optimization_barrier((shard, done))
        return dataclasses.replace(self, shard=shard, dirty=False,
                                   epoch=self.epoch + 1)

    def fence_local(self, token: jax.Array) -> "SharedWindow":
        """Close the epoch with *local* ordering only: the fenced shard
        becomes data-dependent on ``token`` via ``optimization_barrier`` —
        zero wire bytes, value bit-preserving.

        Valid when the epoch's writers and readers live inside ONE jitted
        dataflow (the double-buffered pipeline of ``repro.comm.pipeline``):
        there XLA already orders every store before its data-dependent
        consumers, and the token carries the only extra constraint — buffer
        reuse (a chunk may not reoccupy a buffer its previous tenant still
        feeds).  Cross-step epochs still require the heavy ``fence()``
        (node barrier)."""
        shard, _ = lax.optimization_barrier((self.shard, token))
        return dataclasses.replace(self, shard=shard, dirty=False,
                                   epoch=self.epoch + 1)

    # -- loads ---------------------------------------------------------------
    def _check_clean(self) -> None:
        if self.dirty:
            raise WindowEpochError(
                "read from a dirty SharedWindow: a store/accumulate opened "
                "an epoch that was never closed — call fence() before "
                "reading (paper §6: readers wait for all writers)")

    def read(self) -> jax.Array:
        """Materialize the full node buffer in (local_rank, pod) element
        order — the load from the shared segment (intra-pod gather)."""
        self._check_clean()
        return p.shared_read(self.shard, fast_axis=self.comm.fast_axis,
                             axis=self.axis)

    def read_rank_order(self) -> jax.Array:
        """Full buffer in SMP (pod, local_rank) rank order; needs the
        communicator's static shape."""
        full = self.read()
        if self.comm.pods is None or self.comm.chips is None:
            raise ValueError("read_rank_order needs a Communicator with "
                             "static pods/chips counts")
        return p.shared_to_rank_order(full, num_pods=self.comm.pods,
                                      chips_per_pod=self.comm.chips,
                                      axis=self.axis)


jax.tree_util.register_pytree_node(
    SharedWindow,
    lambda w: ((w.shard,), (w.comm, w.axis, w.epoch, w.dirty)),
    lambda aux, ch: SharedWindow(aux[0], ch[0], axis=aux[1], epoch=aux[2],
                                 dirty=aux[3]))


# ---------------------------------------------------------------------------
# FSDP-style parameter access (the window applied along a weight dim).
# ---------------------------------------------------------------------------

def window_gather(x: jax.Array, dim: Optional[int], fast_axis) -> jax.Array:
    """Load from the pod-shared parameter store: intra-pod all-gather along
    ``dim`` at use time (AD transpose is the reduce-scatter store).
    ``dim=None`` means the tensor is too small to shard — it is replicated
    and the load is free."""
    if dim is None:
        return x
    return p.shared_read(x, fast_axis=fast_axis, axis=dim)


def window_scatter(x: jax.Array, dim: Optional[int], fast_axis) -> jax.Array:
    """Explicit store: reduce-scatter partial contributions back to shards
    (``dim=None``: plain psum of the replicated tensor)."""
    axes = p._axes(fast_axis)
    if dim is None:
        return p.scoped(lax.psum, x, axes)
    return p.scoped(lax.psum_scatter, x, axes, scatter_dimension=dim,
                    tiled=True)
