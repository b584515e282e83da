"""Synchronization primitives (paper §6: heavy barrier vs light-weight flags).

Inside a jitted step, XLA's dataflow already provides the paper's two-barrier
integrity guarantee (a consumer of a gathered/reduced value cannot run before
the exchange).  These helpers exist for *control* synchronization across steps
— checkpoint quiesce, elastic resize, straggler fences — and to make the
paper's two mechanisms explicit and benchmarkable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.substrate.compat import axis_size as _axis_size_one

from repro.comm.primitives import _axes, axis_index, scoped


def barrier(token: jax.Array, axis) -> jax.Array:
    """Heavy-weight barrier: a scalar allreduce over ``axis`` (the paper's
    ``MPI_Barrier(sharedmemComm)``).  Returns a value data-dependent on every
    participant — thread it into downstream computation to enforce ordering."""
    # raw-collective: the barrier primitive itself
    return scoped(lax.psum, token, _axes(axis))


def flag_chain(token: jax.Array, axis) -> jax.Array:
    """Light-weight point-to-point flags (paper §6): a ring of ppermute sends,
    each process waits only for its predecessor.  One hop instead of a full
    reduction tree — cheaper when only neighbor ordering is needed."""
    axes = _axes(axis)
    out = token
    for a in axes:
        n = _axis_size_one(a)
        perm = [(i, (i + 1) % n) for i in range(n)]
        out = scoped(lax.ppermute, out, a, perm)
    return out


def leader_flag(token: jax.Array, *, fast_axis) -> jax.Array:
    """Children signal the leader (chip 0 of the pod) that their partitions
    are ready — the paper's first barrier, light-weight flavor."""
    me = axis_index(fast_axis)
    contrib = jnp.where(me == 0, jnp.zeros_like(token), token)
    # raw-collective: the barrier primitive itself
    return scoped(lax.psum, contrib, _axes(fast_axis))
