"""Communicator: the two-tier communicator as a first-class object.

The paper's setup is ``MPI_Comm_split_type(COMM_TYPE_SHARED)``: the world
communicator splits into a *node* communicator (ranks sharing memory — the
fast tier) and a *bridge* communicator (one leader per node — the slow
tier).  ``Communicator`` carries exactly that structure for a jax mesh:

* ``fast_axis`` — intra-pod tier (ICI / shared memory); one name or a tuple;
* ``slow_axis`` — cross-pod tier (DCN / network), ``None`` on a single node;
* static ``pods``/``chips`` counts when known (rank maps, plan algebra);
* collective methods (``allgather``/``allgatherv``/``broadcast``/
  ``allreduce``/``reduce_scatter``/``alltoall``) that dispatch through the
  scheme registry — ``scheme="naive" | "hier" | "shared" | <future entry>``
  replaces the old per-scheme free functions.

``scheme="auto"`` (the default) resolves the scheme per call through
``repro.comm.tuning``: the committed tuning table where the (family,
topology, size) cell was measured, the ``core.plans`` closed forms where it
was not (see that module's measured -> modeled -> fallback chain).  Because
schemes differ in result CLASS (replicated array vs ``SharedWindow``), call
sites that can only consume one class pass ``result="replicated"`` /
``result="shared"`` — a constraint on the pick, not a scheme name.
Resolution happens at trace time; the lowered program is bit-identical to
calling the chosen concrete scheme directly.

Shared-scheme results come back as a ``SharedWindow`` (ONE copy per node,
sharded over the fast tier) whose ``read()``/``fence()`` carry the paper's
synchronization-epoch semantics; replicated schemes return plain arrays.
Exception: ``allgatherv`` always returns raw ``(blocks, counts)`` — the
irregular result is mediated by ``core.plans.GatherPlan`` compaction, not
by a window.

All methods are shard_map-body operations: call them on local shards inside
a ``shard_map`` (e.g. via ``VirtualCluster.run``/``smap``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax

from repro.comm import primitives as p
from repro.comm import registry
from repro.comm.window import SharedWindow
from repro.core.plans import NodeMap

Axis = Union[str, Sequence[str]]


def _norm(ax: Optional[Axis]):
    if ax is None:
        return None
    if isinstance(ax, (tuple, list)):
        ax = tuple(ax)
        if not ax:
            return None
        return ax if len(ax) > 1 else ax[0]
    return ax


@dataclasses.dataclass(frozen=True)
class Communicator:
    """Two-tier communicator over mesh axis names.

    ``pods``/``chips`` are optional static counts: in-trace collectives work
    without them, but rank maps (``node_map``) and rank-order reads need
    them.  Construct via ``from_cluster`` (tests/bench) or
    ``from_topology`` (production meshes) to get them filled in.
    """

    fast_axis: Axis
    slow_axis: Optional[Axis] = None
    pods: Optional[int] = None
    chips: Optional[int] = None

    def __post_init__(self):
        fast = _norm(self.fast_axis)
        if fast is None:
            raise ValueError("Communicator needs a fast_axis (the node tier)")
        object.__setattr__(self, "fast_axis", fast)
        object.__setattr__(self, "slow_axis", _norm(self.slow_axis))

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_cluster(cls, vc) -> "Communicator":
        """From a ``repro.substrate.VirtualCluster`` (its ``slow`` is already
        ``None`` for single-node shapes)."""
        return cls(fast_axis=vc.fast, slow_axis=vc.slow, pods=vc.pods,
                   chips=vc.chips)

    @classmethod
    def from_topology(cls, topo) -> "Communicator":
        """From a ``repro.core.topology.MeshTopology``: fast tier = every
        non-slow axis, slow tier = the pod axes present."""
        slow = tuple(a for a in topo.slow_axes if a in topo.axis_sizes)
        return cls(fast_axis=topo.fast_axes, slow_axis=slow or None,
                   pods=topo.num_pods, chips=topo.chips_per_pod)

    # -- structure -----------------------------------------------------------
    @property
    def slow(self) -> Optional[Axis]:
        return self.slow_axis

    @property
    def axes(self) -> tuple[str, ...]:
        """Every mesh axis this communicator spans, slow tier first (the
        ``lax.psum`` order of the naive lowering)."""
        return (p._axes(self.slow_axis) if self.slow_axis else ()) + \
            tuple(p._axes(self.fast_axis))

    @property
    def num_nodes(self) -> Optional[int]:
        return self.pods

    @property
    def ranks_per_node(self) -> Optional[int]:
        return self.chips

    @property
    def num_ranks(self) -> Optional[int]:
        if self.pods is None or self.chips is None:
            return None
        return self.pods * self.chips

    @property
    def signature(self) -> Optional[str]:
        """Tuning-table topology signature (``None`` without static
        pods/chips counts).  An elastic rebuild changes this key — the
        re-resolution of ``scheme="auto"`` against the tuning table hangs
        off it (``repro.comm.tuning.retune_for``)."""
        if self.pods is None or self.chips is None:
            return None
        from repro.comm import tuning
        return tuning.signature_for(self)

    @property
    def node_map(self) -> NodeMap:
        """SMP rank->node assignment (``core.plans`` algebra)."""
        if self.pods is None or self.chips is None:
            raise ValueError("node_map needs static pods/chips counts")
        return NodeMap.smp(self.pods, self.chips)

    def split_type_shared(self) -> "Communicator":
        """The node communicator of ``MPI_Comm_split_type(COMM_TYPE_SHARED)``:
        same fast tier, no bridge."""
        return Communicator(fast_axis=self.fast_axis, slow_axis=None,
                            pods=1, chips=self.chips)

    def bridge(self) -> "Communicator":
        """The leaders' bridge communicator: the slow tier as a flat
        single-tier communicator (multi-leader: every chip participates in
        its own shard's bridge exchange)."""
        if self.slow_axis is None:
            raise ValueError("single-node communicator has no bridge tier")
        return Communicator(fast_axis=self.slow_axis, slow_axis=None,
                            pods=1, chips=self.pods)

    # -- in-trace indices ----------------------------------------------------
    def rank(self) -> jax.Array:
        """Flat SMP rank, (pod, chip) row-major — the broadcast root
        numbering."""
        names = (p._axes(self.slow_axis) if self.slow_axis else ()) + \
            p._axes(self.fast_axis)
        return p.axis_index(names)

    def local_rank(self) -> jax.Array:
        return p.axis_index(self.fast_axis)

    def node_rank(self) -> jax.Array:
        if self.slow_axis is None:
            import jax.numpy as jnp
            return jnp.zeros((), jnp.int32)
        return p.axis_index(self.slow_axis)

    # -- dispatch ------------------------------------------------------------
    def _auto_elems(self, family: str, x) -> int:
        """Per-rank payload elems — the tuning table's size normalization
        (alltoall cells are keyed per PAIR, and the local buffer holds one
        chunk per rank)."""
        n = int(x.size)
        if family == "alltoall" and self.num_ranks:
            n = max(1, n // self.num_ranks)
        return n

    def _resolve(self, family: str, scheme: str, x, opts: dict,
                 result: Optional[str], precision: str = "exact",
                 tol: Optional[float] = None) -> tuple[str, dict]:
        """Turn ``scheme="auto"`` into a concrete registry entry (plus its
        recorded tunables; explicit caller opts win).  A concrete scheme
        passes through — but still checked against ``result`` and
        ``precision`` so a constraint can never be silently violated."""
        if scheme != "auto":
            sch = registry.get_scheme(scheme)
            if result is not None and sch.result_class != result:
                raise ValueError(
                    f"scheme {scheme!r} is "
                    f"{sch.result_class}-class but "
                    f"the call requires result={result!r}")
            if sch.precision == "lossy" and precision != "lossy":
                raise ValueError(
                    f"scheme {scheme!r} is lossy but the call did not opt "
                    f"in with precision='lossy'")
            return scheme, opts
        from repro.comm import tuning
        import numpy as np
        dt = np.dtype(x.dtype)
        res = tuning.resolve_for(
            self, family, elems=self._auto_elems(family, x),
            elem_bytes=dt.itemsize, dtype=dt.name, result_class=result,
            precision=precision, tol=tol)
        return res.scheme, {**res.opts, **opts}

    def _call(self, family: str, scheme: str, *args, **kw):
        sch = registry.get_scheme(scheme)
        return sch, sch.op(family)(*args, fast=self.fast_axis,
                                   slow=self.slow_axis, **kw)

    def _wrap(self, sch, out, axis: int):
        if sch.result_class == "shared":
            return SharedWindow(self, out, axis=axis, epoch=1)
        return out

    def allgather(self, x: jax.Array, *, scheme: str = "auto",
                  axis: int = 0, result: Optional[str] = None,
                  precision: str = "exact", tol: Optional[float] = None,
                  **opts):
        """Gather every rank's contribution.  Replicated schemes return the
        full rank-ordered buffer; ``shared`` returns the node's
        ``SharedWindow`` (chip *i* holds shard *i*, (local, pod) order).
        ``**opts`` are scheme tunables (e.g. ``pipelined``'s
        ``n_chunks=``); ``result=`` constrains an ``"auto"`` pick to one
        result class; ``precision="lossy"`` admits quantized wire formats
        (``tol=`` caps their relative error bound)."""
        scheme, opts = self._resolve("allgather", scheme, x, opts, result,
                                     precision, tol)
        sch, out = self._call("allgather", scheme, x, axis=axis, **opts)
        return self._wrap(sch, out, axis)

    def allgatherv(self, x_padded: jax.Array, valid: jax.Array, *,
                   scheme: str = "auto", axis: int = 0,
                   result: Optional[str] = None, precision: str = "exact",
                   tol: Optional[float] = None, **opts):
        """Irregular allgather (padded blocks + valid counts).

        The one family that returns raw ``(blocks, counts)`` for EVERY
        scheme — never a ``SharedWindow``: the irregular result is
        plan-mediated (compaction via ``core.plans.GatherPlan``), not
        window-mediated, matching the paper's counts/displs one-off.
        NOTE the two result classes still differ in block LAYOUT
        (rank-major vs node regions), so auto callers either handle both
        or pass ``result=``."""
        scheme, opts = self._resolve("allgatherv", scheme, x_padded, opts,
                                     result, precision, tol)
        _, out = self._call("allgatherv", scheme, x_padded, valid, axis=axis,
                            **opts)
        return out

    def broadcast(self, x: jax.Array, *, root: int = 0,
                  scheme: str = "auto", axis: int = 0,
                  result: Optional[str] = None, precision: str = "exact",
                  tol: Optional[float] = None, **opts):
        """Broadcast from the flat SMP rank ``root`` (pod, chip row-major).
        ``shared`` returns the node's ``SharedWindow`` of the message."""
        scheme, opts = self._resolve("broadcast", scheme, x, opts, result,
                                     precision, tol)
        sch, out = self._call("broadcast", scheme, x, root=root, axis=axis,
                              **opts)
        return self._wrap(sch, out, axis)

    def allreduce(self, x: jax.Array, *, scheme: str = "auto",
                  axis: int = 0, result: Optional[str] = None,
                  precision: str = "exact", tol: Optional[float] = None,
                  error_feedback=None, **opts):
        """Global sum.  Replicated schemes return the full sum per rank;
        ``shared`` returns it once per node as a ``SharedWindow``.

        ``precision="lossy"`` admits quantized wire formats; with
        ``error_feedback=`` (the carried residual, ``jnp.float32(0)`` to
        start) the call returns ``(sum, new_residual)`` so the local
        quantization error re-enters the next step's payload — the error-
        feedback loop of the gradient bridge.  An exact pick under
        ``"lossy"`` simply absorbs the residual and carries zero."""
        scheme, opts = self._resolve("psum", scheme, x, opts, result,
                                     precision, tol)
        if error_feedback is not None:
            if precision != "lossy":
                raise ValueError(
                    "error_feedback requires precision='lossy'")
            import jax.numpy as jnp
            if registry.get_scheme(scheme).precision == "lossy":
                sch, pair = self._call("psum", scheme, x, axis=axis,
                                       err=error_feedback, **opts)
                out, new_err = pair
            else:
                sch, out = self._call("psum", scheme, x + error_feedback,
                                      axis=axis, **opts)
                new_err = jnp.zeros((), jnp.float32)
            return self._wrap(sch, out, axis), new_err
        sch, out = self._call("psum", scheme, x, axis=axis, **opts)
        return self._wrap(sch, out, axis)

    def reduce_scatter(self, x: jax.Array, *, scheme: str = "auto",
                       axis: int = 0, result: Optional[str] = None,
                       precision: str = "exact", tol: Optional[float] = None,
                       **opts):
        """Sum + scatter.  ``naive``/``pipelined``: every rank gets its flat
        1/R slice; ``shared``: the node's window shards (1/c each,
        bridge-reduced)."""
        scheme, opts = self._resolve("reduce_scatter", scheme, x, opts,
                                     result, precision, tol)
        sch, out = self._call("reduce_scatter", scheme, x, axis=axis, **opts)
        return self._wrap(sch, out, axis)

    def alltoall(self, x: jax.Array, *, scheme: str = "auto", axis: int = 0,
                 result: Optional[str] = None, precision: str = "exact",
                 tol: Optional[float] = None, **opts):
        """Personalized exchange: the local buffer along ``axis`` is R rank-
        ordered chunks; chunk *s* goes to rank *s*.  ``hier`` routes node
        superchunks over the bridge once (P messages instead of P*c), with
        identical results."""
        scheme, opts = self._resolve("alltoall", scheme, x, opts, result,
                                     precision, tol)
        _, out = self._call("alltoall", scheme, x, axis=axis, **opts)
        return out

    # -- async (issue-early / resolve-late) -----------------------------------
    def allgather_async(self, x: jax.Array, *, scheme: str = "auto",
                        axis: int = 0, **opts):
        """Issue the gather now, consume later: returns an
        ``AsyncCollectiveHandle`` whose ``resolve()`` yields the full node
        buffer ((local, pod) order, same as ``SharedWindow.read``).  The
        pick is constrained to the shared result class — the window IS the
        async object; its epoch stands in for the CUDA event, and a store
        between issue and resolve makes ``resolve()`` raise
        ``WindowEpochError`` instead of returning torn bytes."""
        from repro.comm.handle import AsyncCollectiveHandle
        win = self.allgather(x, scheme=scheme, axis=axis, result="shared",
                             **opts)
        return AsyncCollectiveHandle.issue("allgather", win)

    # -- fused collective-matmul (compute overlap) ----------------------------
    def ag_matmul(self, x: jax.Array, w_shard: jax.Array, *,
                  n_chunks: int = 2, use_kernel: bool = False,
                  precision: str = "exact", q4_group: int = 32):
        """``x @ read(window)`` fused: the node-tier gather of the
        contraction-sharded weight streams behind the panel matmuls
        (``repro.comm.pipeline.ag_matmul``).  ``precision="lossy"``
        gathers the weight panels as packed int4 (group size
        ``q4_group``) and dequantizes inside the matmul."""
        from repro.comm import pipeline
        if precision == "lossy":
            return pipeline.ag_matmul_q4(x, w_shard,
                                         fast_axis=self.fast_axis,
                                         n_chunks=n_chunks, group=q4_group,
                                         use_kernel=use_kernel)
        return pipeline.ag_matmul(x, w_shard, fast_axis=self.fast_axis,
                                  n_chunks=n_chunks, use_kernel=use_kernel)

    def ag_matmul_rows(self, a_shard: jax.Array, b: jax.Array, *,
                       n_chunks: int = 2, use_kernel: bool = False):
        """``read(window) @ b`` fused, window sharded along OUTPUT rows
        (e.g. the SUMMA A-panel): per-chunk row panels, no accumulation."""
        from repro.comm import pipeline
        return pipeline.ag_matmul_rows(a_shard, b, fast_axis=self.fast_axis,
                                       n_chunks=n_chunks,
                                       use_kernel=use_kernel)

    def matmul_rs(self, x: jax.Array, w: jax.Array, *, axis: int = 0,
                  n_chunks: int = 2, use_kernel: bool = False):
        """``reduce_scatter(x @ w)`` over the fast tier fused: the scatter
        of panel *k* overlaps the matmul of panel *k+1*."""
        from repro.comm import pipeline
        return pipeline.matmul_rs(x, w, axis_name=self.fast_axis,
                                  scatter_dim=axis, n_chunks=n_chunks,
                                  use_kernel=use_kernel)

    # -- windows & sync -------------------------------------------------------
    def window(self, shard: jax.Array, *, axis: int = 0,
               epoch: int = 0) -> SharedWindow:
        """Wrap an existing node-sharded buffer as a ``SharedWindow``."""
        return SharedWindow(self, shard, axis=axis, epoch=epoch)

    def barrier(self, token: jax.Array) -> jax.Array:
        """Heavy-weight world barrier (``core.sync.barrier`` over both
        tiers)."""
        from repro.core import sync
        names = (p._axes(self.slow_axis) if self.slow_axis else ()) + \
            p._axes(self.fast_axis)
        return sync.barrier(token, names)

    def bridge_psum(self, x):
        """The multi-leader gradient bridge: psum over the slow tier only
        (intra-node reduction already happened via the window transpose).
        Identity on a single node."""
        if self.slow_axis is None:
            return x
        from jax import lax
        return p.scoped(lax.psum, x, p._axes(self.slow_axis))

    # -- step-graph optimizer -------------------------------------------------
    def record(self, *, table=None):
        """Open a step-graph recording against this communicator: record
        collectives (``rec.allreduce``/``rec.gather``), get ``Deferred``
        refs back, then ``rec.run()`` to bucket/dedup/reorder the whole
        schedule and resolve the refs (``repro.comm.stepgraph``)."""
        from repro.comm.stepgraph import GraphRecorder
        return GraphRecorder(self, table=table)

    def apply_schedule(self, schedule, values: dict) -> dict:
        """Execute an already-optimized ``stepgraph.Schedule`` against this
        communicator (``values``: nid -> operand; returns nid -> result)."""
        from repro.comm import stepgraph
        return stepgraph.apply_schedule(self, schedule, values)
