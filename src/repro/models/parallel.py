"""ParallelCtx: how model math maps onto the mesh, in both collective modes.

Models in this framework are written as *local* shard_map bodies against a
``ParallelCtx``.  With ``ctx = ParallelCtx.single()`` every collective helper
is a no-op, so the exact same model code runs on one CPU device (smoke tests)
and on the production mesh.

Axis roles:
  * ``tp_axis``   ("model") — tensor/expert parallelism + sequence-parallel
                  residuals (Megatron-SP layout: activations between blocks
                  are token-sharded over tp).
  * ``fsdp_axes`` — where parameters are *stored*: in **hier** mode (the
                  paper's MPI+MPI scheme) weights live once per pod, sharded
                  over ``data`` (the MPI-3 shared window); in **naive** mode
                  (pure-MPI analogue) they are replicated over data/pod.
  * ``dp_axes``   — batch sharding (("pod","data") or ("data",)).
  * ``pod_axis``  — the bridge (slow tier); gradient reductions cross it once
                  per shard (multi-leader bridge exchange).
  * expert axes   — on the train path an expert layer's experts are spread
                  over data-parallel axes (``expert_axes``): each chip
                  holds its own experts, never gathered, and tokens reach
                  them through the alltoall (``models/moe.py``).

Weight access goes through ``gather_w`` (the "load from the node's shared
buffer": an intra-pod all-gather at use time in hier mode, identity in naive
mode); gradient reduction goes through ``reduce_grads``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm import AsyncCollectiveHandle, Communicator
from repro.comm.handle import _ordered
from repro.comm.primitives import scoped
from repro.comm.window import WindowEpochError


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    tp_axis: Optional[str] = None          # "model"
    fsdp_axes: tuple[str, ...] = ()        # ("data",) in hier mode
    dp_axes: tuple[str, ...] = ()          # ("pod","data") / ("data",)
    dp_sizes: tuple[int, ...] = ()         # sizes of dp_axes
    pod_axis: Optional[str] = None         # "pod" on the multi-pod mesh
    tp: int = 1                            # size of tp_axis
    mode: str = "hier"                     # hier | naive
    compute_dtype: jnp.dtype = jnp.bfloat16
    # beyond-paper perf options (EXPERIMENTS.md §Perf); () = paper-faithful
    #   bf16_rope   — rotate q/k in compute dtype (fp32 angle tables only)
    #   bf16_xent   — bf16 logits, fp32 reductions in the streamed loss
    #   decode2d    — 2D (head-group x seq-group) decode attention: TP-
    #                 stationary attn weights, no per-step FSDP gather
    #   overlap     — fused collective-matmul fast paths: the FSDP window
    #                 read (gather_w) and the SP reduce-scatter (rs_tokens)
    #                 stream chunk-wise behind the adjacent matmul
    #                 (repro.comm.pipeline); overlap_chunks sets the depth
    #   prefetch[=N]— async layer-parameter prefetch: issue layer k+1's FSDP
    #                 window gather while layer k computes, <= N groups in
    #                 flight (default 2); hier mode only, see ParamGroup
    #   stepgraph   — step-graph collective optimizer: record the step's
    #                 whole collective schedule, then bucket small same-axes
    #                 allreduces / dedup gathers / issue-early-resolve-late
    #                 (repro.comm.stepgraph); off by default
    opts: frozenset = frozenset()
    overlap_chunks: int = 2

    @staticmethod
    def single(mode: str = "hier", opts=frozenset()) -> "ParallelCtx":
        return ParallelCtx(mode=mode, compute_dtype=jnp.float32,
                           opts=frozenset(opts))

    def has(self, opt: str) -> bool:
        return opt in self.opts

    @property
    def prefetch(self) -> int:
        """In-flight budget of the layer-parameter prefetcher (0 = off).

        ``"prefetch"`` in opts means budget 2 (double buffering);
        ``"prefetch=N"`` sets it explicitly.  Only meaningful where weights
        actually live in the pod-shared store (hier mode with fsdp axes) —
        elsewhere the gather is free and the prefetcher stays off."""
        if self.mode != "hier" or not self.fsdp_axes:
            return 0
        for o in self.opts:
            if o == "prefetch":
                return 2
            if o.startswith("prefetch="):
                return max(0, int(o[len("prefetch="):]))
        return 0

    @property
    def stepgraph(self) -> bool:
        """Step-graph collective optimizer: the train step records its
        collectives into a ``CollectiveGraph`` and runs the rewritten
        (bucketed / deduped / reordered) schedule instead of issuing
        eagerly.  Bit-identical outputs; off by default."""
        return "stepgraph" in self.opts

    def expert_axes(self, n_experts: int) -> tuple[str, ...]:
        """The data-parallel axes over which a layer's ``n_experts`` are
        spread on the train path: all of ``dp_axes`` where their size is
        above 1 and divides ``n_experts``, else ``()`` (every chip then
        holds every expert).  Every function that makes a ctx sets
        ``dp_sizes``."""
        if len(self.dp_sizes) != len(self.dp_axes):
            raise ValueError(f"dp_sizes {self.dp_sizes} do not give the "
                             f"sizes of dp_axes {self.dp_axes}")
        n = 1
        for size in self.dp_sizes:
            n *= size
        return self.dp_axes if n > 1 and n_experts % n == 0 else ()

    def grad_sq_norm(self, grads, metas, node: Communicator,
                     world: Communicator, **allreduce):
        """The squared global norm of reduced, node-resident gradients,
        every element counted exactly once.  A leaf is tiled over the axes
        it is sharded on and replicated over the rest of the node tier:
        its square is weighted by 1/replication and summed over the node.
        Gradients are pod-identical after the bridge, except expert leaves
        held apart over the pods, whose squares sum over every chip.
        ``allreduce``: keywords of both reductions."""
        gsq, gsq_pods = jnp.float32(0.0), None
        for g, meta in zip(jax.tree.leaves(grads), metas):
            held = set(meta.ep_axes)
            if self.mode == "hier" and meta.fsdp_dim is not None:
                held |= set(self.fsdp_axes)
            repl = 1.0
            if meta.tp_dim is None and self.tp_axis:
                repl *= self.tp
            for a, size in zip(self.dp_axes, self.dp_sizes):
                if a != self.pod_axis and a not in held:
                    repl *= size
            sq = jnp.sum(jnp.square(g.astype(jnp.float32))) / repl
            if self.pod_axis and self.pod_axis in meta.ep_axes:
                gsq_pods = sq if gsq_pods is None else gsq_pods + sq
            else:
                gsq += sq
        gsq = node.allreduce(gsq, **allreduce)
        if gsq_pods is not None:
            gsq += world.allreduce(gsq_pods, **allreduce)
        return gsq

    # ---- indices -----------------------------------------------------------
    @property
    def tp_rank(self):
        return lax.axis_index(self.tp_axis) if self.tp_axis else 0

    def tp_group_rank(self, group: int):
        """(outer, inner) coords when the tp axis is factored as
        (tp//group, group): outer = rank // group, inner = rank % group."""
        r = self.tp_rank
        return r // group, r % group

    # ---- the data-tier communicator -----------------------------------------
    @property
    def comm(self) -> Optional[Communicator]:
        """The two-tier communicator of the parameter/gradient data path:
        fast tier = where parameters are stored (fsdp in hier mode, the
        non-pod dp axes in naive mode), slow tier = the bridge.  ``None``
        for a single-device ctx."""
        fast = self.fsdp_axes or tuple(a for a in self.dp_axes
                                       if a != self.pod_axis)
        if not fast:
            return None
        return Communicator(fast_axis=fast, slow_axis=self.pod_axis)

    # ---- weight load/store (the shared-memory window) -----------------------
    def gather_w(self, w: jax.Array, fsdp_dim: Optional[int]) -> jax.Array:
        """Load a weight from the pod-shared store.  hier: read through the
        node's ``SharedWindow`` — intra-pod all-gather of the FSDP shards at
        use time (cast first so bf16 moves, not fp32); AD transposes the
        read into the reduce-scatter store.  naive: local private copy, no
        traffic."""
        w = w.astype(self.compute_dtype)
        if self.mode == "hier" and self.fsdp_axes and fsdp_dim is not None:
            w = self.comm.window(w, axis=fsdp_dim, epoch=1).read()
        return w

    def ag_matmul(self, x: jax.Array, w: jax.Array,
                  fsdp_dim: Optional[int]) -> jax.Array:
        """``x @ gather_w(w, fsdp_dim)`` — the fused gather_w fast path.

        With the ``overlap`` opt (hier mode, weight FSDP-sharded along its
        contraction dim), the window read streams chunk-wise behind the
        panel matmuls (``comm.ag_matmul``); otherwise exactly the unfused
        read-then-matmul."""
        fusable = (self.has("overlap") and self.mode == "hier"
                   and bool(self.fsdp_axes) and fsdp_dim == 0
                   and w.ndim == 2)
        if fusable:
            shard = w.astype(self.compute_dtype)
            nc = _clamp_chunks(self.overlap_chunks, shard.shape[0])
            return self.comm.ag_matmul(x, shard, n_chunks=nc)
        return x @ self.gather_w(w, fsdp_dim)

    def matmul_rs(self, x: jax.Array, w: jax.Array, dim: int = 1
                  ) -> jax.Array:
        """``rs_tokens(x @ w, dim)`` — the fused rs_tokens fast path.

        With the ``overlap`` opt, the token-dim reduce-scatter of panel *k*
        overlaps the matmul of panel *k+1* (``comm.pipeline.matmul_rs``);
        otherwise exactly the unfused matmul-then-scatter."""
        if not self.tp_axis:
            return x @ w
        if self.has("overlap"):
            nc = _clamp_chunks(self.overlap_chunks,
                               x.shape[dim] // self.tp)
            if nc > 1:
                tp_comm = Communicator(fast_axis=self.tp_axis)
                return tp_comm.matmul_rs(x, w, axis=dim, n_chunks=nc)
        return self.rs_tokens(x @ w, dim)

    def grad_reduce_axes(self, meta) -> tuple[str, ...]:
        """Axes a gradient leaf still needs to be summed over — the single
        source of truth the step-graph optimizer rewrites under.

        The AD transpose of the hier weight gather already reduce-scattered
        over the fsdp axes; tp-sharded weights never replicate over the tp
        axis.  What is left: the bridge (pod) in hier mode — plus the fsdp
        axes for the tiny fsdp-replicated leaves (norms); the full dp tier
        in naive mode; plus the tp axis for tp-replicated leaves in both.
        An expert leaf is summed over none of the axes its experts are
        spread over (``PMeta.ep_axes``): one chip alone holds each expert.
        Bridge axes come FIRST so the naive lowering (``lax.psum`` over
        slow + fast) matches the axes order exactly."""
        axes: tuple[str, ...] = ()
        if self.mode == "hier":
            if self.pod_axis:
                axes += (self.pod_axis,)
            if meta.fsdp_dim is None and self.fsdp_axes:
                axes += tuple(self.fsdp_axes)
        else:
            axes += tuple(self.dp_axes)
        axes = tuple(a for a in axes if a not in meta.ep_axes)
        if meta.tp_dim is None and self.tp_axis:
            axes += (self.tp_axis,)
        return axes

    def axes_comm(self, axes: tuple[str, ...]) -> Communicator:
        """The two-tier communicator that reduces over EXACTLY ``axes``:
        pod is the slow tier when present alongside fast axes, else the
        whole (single-tier) communicator."""
        fast = tuple(a for a in axes if a != self.pod_axis)
        slow = self.pod_axis if (self.pod_axis in axes and fast) else None
        return Communicator(fast_axis=fast or axes, slow_axis=slow)

    def reduce_grads(self, grads, metas=None, *, compress=None,
                     recorder=None, precision: str = "exact",
                     tol: Optional[float] = None, error_state=None):
        """Bridge gradient reduction.  Gradients already match the param
        layout w.r.t. data (AD transposes the hier window reads into
        intra-pod reduce-scatters); what remains is the cross-pod (bridge)
        psum in hier mode, or the flat dp allreduce in naive mode.

        With ``metas`` (a leaf-aligned ``PMeta`` sequence) the reduction is
        per-leaf over ``grad_reduce_axes(meta)`` through ``Communicator``
        dispatch — the schedule-driven path.  ``precision="lossy"`` routes
        bridge-crossing leaves (hier mode) through the quantized wire
        formats of the scheme registry (auto-resolved, never named here);
        ``error_state`` (a grads-shaped tree of residuals, scalar
        ``jnp.float32(0)`` leaves to start) threads error feedback through
        those reductions, and the call then returns
        ``(grads, new_error_state)``.  ``compress`` is the legacy explicit
        hook (same leaves, caller-supplied fn); ``recorder`` (a
        ``Communicator.record()`` ``GraphRecorder``) defers every exact
        reduction into the step graph and returns ``Deferred`` leaves —
        resolve them with the ``ScheduleResult`` of ``recorder.run()``.
        Without ``metas``: the legacy whole-tree reduction (every leaf
        crosses the same axes)."""
        lossy = precision == "lossy"
        if error_state is not None and not lossy:
            raise ValueError("error_state requires precision='lossy'")
        errs = jax.tree.leaves(error_state) \
            if error_state is not None else None
        if metas is not None:
            leaves = jax.tree.leaves(grads)
            new_errs = [jnp.zeros((), jnp.float32) for _ in leaves]
            reduced, comms, lossy_comms = [], {}, {}
            for i, (g, meta) in enumerate(zip(leaves, metas)):
                axes = self.grad_reduce_axes(meta)
                if not axes:
                    reduced.append(g)
                    continue
                # bridge compression: the slow-tier (cross-pod) reduction
                # is quantized; on podless meshes it applies to every dp
                # reduction (keeps the path exercised at small scale).
                bridge = (self.pod_axis in axes) if self.pod_axis else True
                if compress is not None and self.mode == "hier" and bridge:
                    reduced.append(compress(g, axes))
                    continue
                if lossy and self.mode == "hier" and bridge:
                    # single-tier over EXACTLY axes: quantize once over the
                    # whole reduction (the legacy compress semantics —
                    # arbitrary leaf shapes flatten+pad into blocks).
                    comm = lossy_comms.get(axes)
                    if comm is None:
                        comm = lossy_comms[axes] = \
                            Communicator(fast_axis=axes)
                    if errs is not None:
                        out, new_errs[i] = comm.allreduce(
                            g, precision="lossy", tol=tol,
                            result="replicated", error_feedback=errs[i])
                    else:
                        out = comm.allreduce(g, precision="lossy", tol=tol,
                                             result="replicated")
                    reduced.append(out)
                    continue
                if recorder is not None:
                    reduced.append(recorder.allreduce(
                        g, axes=axes, scheme="naive", key=("grad", i)))
                    continue
                comm = comms.get(axes)
                if comm is None:
                    comm = comms[axes] = self.axes_comm(axes)
                reduced.append(comm.allreduce(g, scheme="naive",
                                              result="replicated"))
            tree = jax.tree.unflatten(jax.tree.structure(grads), reduced)
            if error_state is not None:
                return tree, jax.tree.unflatten(
                    jax.tree.structure(grads), new_errs)
            return tree
        if self.mode == "hier":
            if self.pod_axis is None:
                return (grads, error_state) if error_state is not None \
                    else grads
            if lossy:
                bcomm = Communicator(fast_axis=self.pod_axis)
                leaves = jax.tree.leaves(grads)
                if errs is not None:
                    pairs = [bcomm.allreduce(g, precision="lossy", tol=tol,
                                             result="replicated",
                                             error_feedback=e)
                             for g, e in zip(leaves, errs)]
                    st = jax.tree.structure(grads)
                    return (jax.tree.unflatten(st, [o for o, _ in pairs]),
                            jax.tree.unflatten(st, [e for _, e in pairs]))
                return jax.tree.map(
                    lambda g: bcomm.allreduce(g, precision="lossy", tol=tol,
                                              result="replicated"), grads)
            comm = self.comm
            if comm is None:     # no node tier: the bridge is the whole comm
                comm = Communicator(fast_axis=self.pod_axis)
                return jax.tree.map(
                    lambda g: comm.allreduce(g, result="replicated"), grads)
            return jax.tree.map(comm.bridge_psum, grads)
        axes = self.dp_axes
        if not axes:
            return grads
        if error_state is not None:
            raise ValueError("error_state needs the hier bridge path "
                             "(metas, or hier mode)")
        # the dp reduction's own communicator: reduce over EXACTLY dp_axes.
        # scheme="auto" + the replicated constraint: the tuning table (or
        # the closed forms) picks the reduction schedule, but the result
        # must stay a plain per-rank gradient, never a window.
        fast = tuple(a for a in axes if a != self.pod_axis)
        slow = self.pod_axis if (self.pod_axis in axes and fast) else None
        dp_comm = Communicator(fast_axis=fast or axes, slow_axis=slow)
        return jax.tree.map(
            lambda g: dp_comm.allreduce(g, result="replicated",
                                        precision=precision, tol=tol),
            grads)

    # ---- tp collectives ------------------------------------------------------
    def ag_tokens(self, x: jax.Array, dim: int = 1) -> jax.Array:
        """Sequence-parallel all-gather: (B, T/tp, d) -> (B, T, d).
        Output is checkpoint-named so the save_ag remat policy can keep it
        across the bwd instead of re-gathering (§Perf)."""
        if not self.tp_axis:
            return x
        from jax.ad_checkpoint import checkpoint_name
        # raw-collective: ag_tokens tp fast path
        out = scoped(lax.all_gather,
                     x, self.tp_axis, axis=dim, tiled=True)
        return checkpoint_name(out, "ag_out")

    def rs_tokens(self, x: jax.Array, dim: int = 1) -> jax.Array:
        """Sequence-parallel reduce-scatter: partial (B, T, d) -> (B, T/tp, d)."""
        if not self.tp_axis:
            return x
        return scoped(lax.psum_scatter, x, self.tp_axis,
                      scatter_dimension=dim, tiled=True)

    def psum_tp(self, x: jax.Array) -> jax.Array:
        if not self.tp_axis:
            return x
        # raw-collective: psum_tp fast path
        return scoped(lax.psum, x, self.tp_axis)

    def group_all_gather(self, x: jax.Array, *, group: int, dim: int
                         ) -> jax.Array:
        """All-gather within contiguous subgroups of the tp axis (the
        axis_index_groups trick used for mLSTM head groups and split-K)."""
        if not self.tp_axis or group == 1:
            return x
        n = self.tp
        groups = [list(range(s, s + group)) for s in range(0, n, group)]
        return scoped(lax.all_gather,  # raw-collective: grouped tp fast path
                      x, self.tp_axis, axis=dim, tiled=True,
                      axis_index_groups=groups)

    def group_psum(self, x: jax.Array, *, group: int) -> jax.Array:
        if not self.tp_axis or group == 1:
            return x
        n = self.tp
        groups = [list(range(s, s + group)) for s in range(0, n, group)]
        return scoped(lax.psum,  # raw-collective: grouped tp fast path
                      x, self.tp_axis, axis_index_groups=groups)

    def pmax_tp(self, x: jax.Array) -> jax.Array:
        """Cross-shard max.  Implemented as all_gather+max rather than pmax:
        pmax has no JVP rule, and this shows up inside differentiated loss
        code (as a softmax stabilizer)."""
        if not self.tp_axis:
            return x
        # raw-collective: pmax_tp tp fast path
        g = scoped(lax.all_gather, x, self.tp_axis)   # (tp, ...)
        return jnp.max(g, axis=0)

    # ---- sizes ---------------------------------------------------------------
    def shard(self, n: int) -> int:
        assert n % self.tp == 0, f"{n} not divisible by tp={self.tp}"
        return n // self.tp


# ---------------------------------------------------------------------------
# Async parameter prefetch (FSDP2-style sharded <-> unsharded lifecycle)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ParamGroup:
    """One layer's parameters as an unshard/reshard unit.

    Mirrors torch FSDP2's ``_fsdp_param_group``: a group's weights live
    *sharded* in the pod store; ``unshard()`` issues every FSDP-dim gather
    as an ``AsyncCollectiveHandle`` (no data consumed yet), ``wait()``
    resolves the handles into the full per-layer tree, ``reshard()`` drops
    the full copy so at most ``budget`` groups are ever unsharded.

    The gather per leaf is byte-identical to ``ParallelCtx.gather_w`` (cast
    to compute dtype FIRST, then read through the window), so prefetched and
    eager execution produce bit-identical math.
    """

    ctx: ParallelCtx
    params: object                 # this layer's (sharded) param tree
    metas: object                  # matching tree with PMeta leaves
    _handles: object = None        # issued but unresolved (in-flight)
    _full: object = None           # resolved full copy (unsharded)

    @property
    def state(self) -> str:
        """sharded -> in_flight -> unsharded lifecycle probe (tests)."""
        if self._full is not None:
            return "unsharded"
        return "in_flight" if self._handles is not None else "sharded"

    def unshard(self) -> "ParamGroup":
        """Issue the group's gathers (idempotent while in flight).

        The whole group shares ONE ordering token — the analogue of FSDP2
        recording a single CUDA event per param-group bucket rather than
        one per tensor: the leaves gather independently, one barrier pins
        "all of this group's gathers have issued" (2 barrier ops per group
        instead of 2 per leaf — measurably cheaper in the step bench)."""
        if self._handles is not None or self._full is not None:
            return self
        ctx = self.ctx

        def read(w, m):
            w = w.astype(ctx.compute_dtype)
            dim = getattr(m, "fsdp_dim", None)
            if ctx.mode == "hier" and ctx.fsdp_axes and dim is not None:
                win = ctx.comm.window(w, axis=dim, epoch=1)
                return (win, win.read())
            return w

        read_tree = jax.tree.map(read, self.params, self.metas)
        is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
        pairs = [p for p in jax.tree.leaves(read_tree, is_leaf=is_pair)
                 if is_pair(p)]
        if pairs:
            vals, token = _ordered(tuple(v for _, v in pairs),
                                   jnp.ones((), jnp.float32))
        else:
            vals, token = (), None
        it = iter(zip((w for w, _ in pairs), vals))

        def to_handle(p):
            if not is_pair(p):
                return p
            win, v = next(it)
            return AsyncCollectiveHandle(
                family="allgather", window=win, value=v, token=token,
                issue_epoch=win.epoch)

        self._handles = jax.tree.map(to_handle, read_tree, is_leaf=is_pair)
        return self

    def wait(self):
        """Resolve the in-flight gathers; returns the full param tree.

        The group resolves as a unit (one barrier against the shared issue
        token); each handle's epoch is still checked individually, so a
        store tearing ONE window fails the wait exactly like a per-leaf
        ``resolve`` would."""
        if self._full is None:
            assert self._handles is not None, \
                "ParamGroup.wait() before unshard()"
            is_h = lambda x: isinstance(x, AsyncCollectiveHandle)  # noqa: E731
            handles = [h for h in jax.tree.leaves(self._handles, is_leaf=is_h)
                       if is_h(h)]
            for h in handles:
                if not h.done:
                    raise WindowEpochError(
                        f"wait on a torn {h.family} handle: the window was "
                        f"stored to or fenced past epoch {h.issue_epoch} "
                        f"(now epoch {h.window.epoch}, "
                        f"dirty={h.window.dirty}) — re-issue after the "
                        "fence")
            if handles:
                vals, _ = _ordered(tuple(h.value for h in handles),
                                   handles[0].token)
            it = iter(vals) if handles else iter(())

            def resolve(h):
                return next(it) if is_h(h) else h

            self._full = jax.tree.map(resolve, self._handles, is_leaf=is_h)
            self._handles = None
        return self._full

    def reshard(self) -> "ParamGroup":
        """Free the unsharded copy (back to the sharded store)."""
        self._full = None
        self._handles = None
        return self


def prefetch_schedule(n: int, budget: int) -> list[tuple[str, int]]:
    """The prefetcher's event order for ``n`` groups with at most
    ``budget`` in flight: prime ``budget`` unshards, then per group —
    wait, compute, reshard, and backfill the next unshard.  Pure data so
    the in-flight invariants are property-testable without tracing."""
    budget = max(1, budget)
    events = [("unshard", k) for k in range(min(budget, n))]
    for k in range(n):
        events.append(("wait", k))
        events.append(("compute", k))
        events.append(("reshard", k))
        if k + budget < n:
            events.append(("unshard", k + budget))
    return events


def prefetch_walk(groups, fn, x, budget: int):
    """Drive ``x = fn(x, k, full_params_k)`` over ``groups`` with the
    bounded-prefetch schedule.  Inside one jitted step the issued gathers
    overlap the preceding groups' compute via XLA dataflow — the FSDP2
    implicit-prefetch pattern."""
    groups = list(groups)
    for ev, k in prefetch_schedule(len(groups), budget):
        if ev == "unshard":
            groups[k].unshard()
        elif ev == "wait":
            groups[k].wait()
        elif ev == "compute":
            x = fn(x, k, groups[k].wait())
        else:
            groups[k].reshard()
    return x


def _clamp_chunks(n_chunks: int, extent: int) -> int:
    """Largest chunk count <= ``n_chunks`` that tiles ``extent`` (the fused
    paths must never change shapes — they fall back to fewer chunks)."""
    nc = max(1, min(n_chunks, extent if extent > 0 else 1))
    while extent % nc:
        nc -= 1
    return nc


def tp_slice(x: jax.Array, rank, tp: int, dim: int) -> jax.Array:
    """Dynamic slice of the tp-local piece along ``dim`` (used where a weight
    is stored unsharded but consumed shard-wise)."""
    size = x.shape[dim] // tp
    start = [0] * x.ndim
    start[dim] = rank * size
    sizes = list(x.shape)
    sizes[dim] = size
    return lax.dynamic_slice(x, start, sizes)
