"""Distributed train / prefill / decode steps (shard_map over the mesh).

This is where the paper's two schemes become end-to-end training modes:

* ``mode="hier"``  — parameters + optimizer state live ONCE per pod, sharded
  over the ``data`` axis (the MPI-3 shared window); layer weights are
  all-gathered intra-pod at use (children load from the node buffer); the
  gradient bridge is: AD-transposed intra-pod reduce-scatter, then ONE
  cross-pod psum per shard (the multi-leader bridge exchange).
* ``mode="naive"`` — pure-MPI analogue: every chip a full private replica,
  one flat (pod, data) psum per gradient.

TP ("model" axis) sharding is identical in both — the paper keeps
computational parallelism unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.substrate.compat import shard_map

from repro.comm import Communicator
from repro.core.topology import MeshTopology
from repro.models.meta import PMeta
from repro.models.parallel import ParallelCtx
from repro.models.transformer import Model, build
from repro.optim.adamw import adamw_init, adamw_update
from repro.configs.base import ModelConfig


def make_ctx(topo: MeshTopology, mode: str,
             compute_dtype=jnp.bfloat16, opts=()) -> ParallelCtx:
    has_pod = "pod" in topo.axis_sizes
    dp = ("pod", "data") if has_pod else ("data",)
    return ParallelCtx(
        tp_axis="model",
        fsdp_axes=("data",) if mode == "hier" else (),
        dp_axes=dp,
        dp_sizes=tuple(topo.size(a) for a in dp),
        pod_axis="pod" if has_pod else None,
        tp=topo.size("model"),
        mode=mode,
        compute_dtype=compute_dtype,
        opts=frozenset(opts))


def build_model(cfg: ModelConfig, topo: MeshTopology, mode: str,
                compute_dtype=jnp.bfloat16, opts=()) -> Model:
    ctx = make_ctx(topo, mode, compute_dtype, opts)
    return build(cfg, ctx, data=topo.size("data"))


# ---------------------------------------------------------------------------
# Sharding specs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, topo: MeshTopology) -> dict:
    dp = ("pod", "data") if "pod" in topo.axis_sizes else ("data",)
    dp = tuple(a for a in dp if a in topo.axis_sizes)
    if cfg.frontend == "encodec":
        return {"frames": P(dp), "labels": P(dp)}
    out = {"tokens": P(dp)}
    if cfg.frontend == "vit":
        out["patches"] = P(dp)
    return out


def grad_reduce_axes(meta: PMeta, ctx: ParallelCtx) -> tuple[str, ...]:
    """Axes a gradient leaf still needs to be summed over.

    Thin wrapper over ``ParallelCtx.grad_reduce_axes`` — the logic moved
    there so ``reduce_grads`` and the step-graph optimizer share one source
    of truth; this spelling stays for existing callers.
    """
    return ctx.grad_reduce_axes(meta)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainStepBundle:
    fn: Any                 # jittable (state, batch) -> (state, metrics)
    state_specs: Any
    batch_spec: Any
    model: Model
    mesh: Any

    def init_state(self, seed: int = 0):
        """Fresh train state, built in place: each device materializes only
        its own shard of ``state_specs`` over ``mesh``."""
        def init():
            params = self.model.init_params(seed)
            m, v = adamw_init(params)
            return {"params": params, "m": m, "v": v,
                    "step": jnp.zeros((), jnp.int32)}

        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                 self.state_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        return jax.jit(init, out_shardings=shardings)()


def make_train_step(cfg: ModelConfig, topo: MeshTopology, mesh, *,
                    mode: str = "hier", lr: float = 3e-4,
                    weight_decay: float = 0.1, clip: float = 1.0,
                    unroll: int = 1, compress=None, opts=(),
                    compute_dtype=jnp.bfloat16) -> TrainStepBundle:
    model = build_model(cfg, topo, mode, compute_dtype, opts)
    # the int8_bridge opt is now a precision constraint, not a function:
    # auto-resolution picks the quantized wire scheme from the registry
    grad_precision = "lossy" if (compress is None
                                 and "int8_bridge" in opts) else "exact"
    ctx = model.ctx
    defs = model.defs
    pspecs = model.param_specs()
    bspec = batch_specs(cfg, topo)
    state_specs = {"params": pspecs, "m": pspecs, "v": pspecs, "step": P()}
    meta_leaves = jax.tree.leaves(defs,
                                  is_leaf=lambda x: isinstance(x, PMeta))
    # world communicator over the whole mesh: metric reductions cross both
    # tiers; the grad-norm reduction is node-local (pods hold identical
    # grads after the bridge), i.e. the split_type(SHARED) communicator.
    world = Communicator.from_topology(topo)
    node = world.split_type_shared()

    from repro.models.transformer import _loss  # local-body entry

    def body(state, batch):
        params = state["params"]

        def lf(p):
            loss, cnt, loads = _loss(cfg, ctx, defs, p, batch, unroll=unroll,
                                     stats=True)
            return loss, (cnt, loads)

        (loss_sum, (cnt, loads)), grads = jax.value_and_grad(
            lf, has_aux=True)(params)
        # scheme="auto": the tuning table picks the reduction schedule per
        # topology/size; the replicated constraint (not a scheme name)
        # keeps the result a plain per-rank scalar, never a window.
        # The gradient bridge (the paper's scheme vs the flat pure-MPI
        # reduce) goes through ctx.reduce_grads; with the stepgraph opt the
        # whole schedule is recorded first, then bucketed/reordered and run
        # as one optimized schedule — outputs bit-identical either way.
        if ctx.stepgraph:
            rec = world.record()
            rl = rec.allreduce(loss_sum, axes=world.axes, scheme="auto",
                               result="replicated", bucketable=False,
                               key="loss")
            rc = rec.allreduce(cnt, axes=world.axes, scheme="auto",
                               result="replicated", bucketable=False,
                               key="cnt")
            grads = ctx.reduce_grads(grads, meta_leaves, compress=compress,
                                     recorder=rec,
                                     precision=grad_precision)
            res = rec.run()
            loss_g, cnt_g = res[rl], res[rc]
            grads = res.resolve(grads)
        else:
            loss_g = world.allreduce(loss_sum, result="replicated")
            cnt_g = world.allreduce(cnt, result="replicated")
            grads = ctx.reduce_grads(grads, meta_leaves, compress=compress,
                                     precision=grad_precision)
        with jax.named_scope("optimizer"):
            grads = jax.tree.map(lambda g: g / cnt_g, grads)

            gsq = ctx.grad_sq_norm(grads, meta_leaves, node, world,
                                   result="replicated")
            gnorm = jnp.sqrt(gsq)
            scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)

            new_params, new_m, new_v = adamw_update(
                params, grads, state["m"], state["v"], state["step"] + 1,
                lr=lr, weight_decay=weight_decay)
        new_state = {"params": new_params, "m": new_m, "v": new_v,
                     "step": state["step"] + 1}
        metrics = {"loss": loss_g / cnt_g, "gnorm": gnorm, "tokens": cnt_g}
        if loads is not None:
            # the busiest expert's routed rows over the mean, worst layer
            loads = world.allreduce(loads, result="replicated")
            metrics["moe_load_max"] = jnp.max(
                jnp.max(loads, axis=1) / jnp.mean(loads, axis=1))
        return new_state, metrics

    metric_specs = {"loss": P(), "gnorm": P(), "tokens": P()}
    if cfg.moe:
        metric_specs["moe_load_max"] = P()
    smapped = shard_map(
        body, mesh=mesh, in_specs=(state_specs, bspec),
        out_specs=(state_specs, metric_specs), check_vma=False)
    return TrainStepBundle(fn=smapped, state_specs=state_specs,
                           batch_spec=bspec, model=model, mesh=mesh)


# ---------------------------------------------------------------------------
# End-to-end step-time bench body (the repro.bench "step_time" family)
# ---------------------------------------------------------------------------

def cluster_ctx(vc, *, mode: str = "hier", compute_dtype=jnp.float32,
                opts=()) -> ParallelCtx:
    """A ``ParallelCtx`` over a bench ``VirtualCluster``'s OWN axis names.

    ``make_ctx`` hardcodes the production ``("pod", "data", "model")`` mesh;
    the bench topology matrix names its axes per cluster.  Mapping: the slow
    tier is the bridge, the fast tier is where parameters are stored — and
    when the fast tier is factored over several axes (the ``(dp, tp)``
    tuple mesh) the LAST fast axis plays tensor-parallel, mirroring the
    production layout.
    """
    if len(vc.slow_names) > 1:
        raise ValueError("cluster_ctx supports at most one slow (bridge) "
                         f"axis, got {vc.slow_names}")
    pod = vc.slow_names[0] if vc.slow_names else None
    fast = vc.fast_names
    tp_axis = fast[-1] if len(fast) > 1 else None
    store = fast[:-1] if len(fast) > 1 else fast
    store_size = 1
    for name, size in zip(vc.axis_names, vc.axis_shapes):
        if name in store:
            store_size *= size
    if store_size == 1:
        # a size-1 store shards nothing, so there is no window gather to
        # issue early: the prefetch schedule degrades to the eager path
        # (same program) instead of paying the handle plumbing for no-ops
        opts = tuple(o for o in opts if not str(o).startswith("prefetch"))
    dp = ((pod,) + store) if pod else store
    sizes = dict(zip(vc.axis_names, vc.axis_shapes))
    return ParallelCtx(
        tp_axis=tp_axis,
        fsdp_axes=store if mode == "hier" else (),
        dp_axes=dp,
        dp_sizes=tuple(sizes[a] for a in dp),
        pod_axis=pod,
        tp=vc.fast_shape[-1] if tp_axis else 1,
        mode=mode, compute_dtype=compute_dtype, opts=frozenset(opts))


def make_cluster_train_step(cfg: ModelConfig, vc, *, mode: str = "hier",
                            lr: float = 3e-4, weight_decay: float = 0.1,
                            clip: float = 1.0, unroll: int = 1,
                            global_batch: int = 8, opts=(),
                            compute_dtype=jnp.float32) -> TrainStepBundle:
    """``make_train_step`` over a ``VirtualCluster``'s OWN mesh and axis
    names — the elastic runtime's step builder.

    After a pod loss the runtime calls this again with the SURVIVING
    cluster: ``cluster_ctx`` re-maps the tiers, the world communicator is
    rebuilt via ``Communicator.from_cluster`` (the blessed constructor —
    static pods/chips counts feed the tuning-table signature), and
    ``scheme="auto"`` re-resolves against the new signature at trace time.
    When ``global_batch`` does not divide the surviving data-parallel rank
    count (e.g. 8 ranks -> 7 after a node loss), the batch is REPLICATED
    instead of sharded — every rank computes the full batch and the
    ``cnt`` normalization absorbs the overcount, so the update math is
    unchanged and no topology is unreachable after a shrink.
    """
    if cfg.frontend not in (None, "", "tokens"):
        raise ValueError(f"cluster train step only drives the token "
                         f"frontend, not {cfg.frontend!r}")
    ctx = cluster_ctx(vc, mode=mode, compute_dtype=compute_dtype, opts=opts)
    sizes = dict(zip(vc.axis_names, vc.axis_shapes))
    data = 1
    for a in (ctx.fsdp_axes or tuple(a for a in ctx.dp_axes
                                     if a != ctx.pod_axis)):
        data *= sizes[a]
    model = build(cfg, ctx, data=data)
    defs = model.defs
    pspecs = model.param_specs(tp_axis=ctx.tp_axis,
                               fsdp_axis=ctx.fsdp_axes[0]
                               if ctx.fsdp_axes else None)
    state_specs = {"params": pspecs, "m": pspecs, "v": pspecs, "step": P()}
    n_dp = 1
    for a in ctx.dp_axes:
        n_dp *= sizes[a]
    shard_batch = global_batch % n_dp == 0
    bspec = {"tokens": P(ctx.dp_axes) if shard_batch else P()}
    meta_leaves = jax.tree.leaves(defs,
                                  is_leaf=lambda x: isinstance(x, PMeta))
    world = Communicator.from_cluster(vc)
    node = world.split_type_shared()

    from repro.models.transformer import _loss  # local-body entry

    def body(state, batch):
        params = state["params"]

        def lf(p):
            return _loss(cfg, ctx, defs, p, batch, unroll=unroll)

        (loss_sum, cnt), grads = jax.value_and_grad(lf, has_aux=True)(params)
        # scheme="auto" + replicated constraint, exactly as the production
        # train step: post-shrink this re-resolves against the NEW topology
        # signature (measured entries where the bench swept it, modeled
        # closed forms where it did not).
        if ctx.stepgraph:
            rec = world.record()
            rl = rec.allreduce(loss_sum, axes=world.axes, scheme="auto",
                               result="replicated", bucketable=False,
                               key="loss")
            rc = rec.allreduce(cnt, axes=world.axes, scheme="auto",
                               result="replicated", bucketable=False,
                               key="cnt")
            grads = ctx.reduce_grads(grads, meta_leaves, recorder=rec)
            res = rec.run()
            loss_g, cnt_g = res[rl], res[rc]
            grads = res.resolve(grads)
        else:
            loss_g = world.allreduce(loss_sum, result="replicated")
            cnt_g = world.allreduce(cnt, result="replicated")
            grads = ctx.reduce_grads(grads, meta_leaves)
        with jax.named_scope("optimizer"):
            grads = jax.tree.map(lambda g: g / cnt_g, grads)
            gsq = ctx.grad_sq_norm(grads, meta_leaves, node, world,
                                   result="replicated")
            gnorm = jnp.sqrt(gsq)
            scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
            new_params, new_m, new_v = adamw_update(
                params, grads, state["m"], state["v"], state["step"] + 1,
                lr=lr, weight_decay=weight_decay)
        new_state = {"params": new_params, "m": new_m, "v": new_v,
                     "step": state["step"] + 1}
        metrics = {"loss": loss_g / cnt_g, "gnorm": gnorm, "tokens": cnt_g}
        return new_state, metrics

    smapped = vc.smap(body, in_specs=(state_specs, bspec),
                      out_specs=(state_specs,
                                 {"loss": P(), "gnorm": P(), "tokens": P()}))
    return TrainStepBundle(fn=smapped, state_specs=state_specs,
                           batch_spec=bspec, model=model, mesh=vc.mesh)


def make_step_bench(cfg: ModelConfig, vc, *, opts=(), unroll: int = 1,
                    lr: float = 3e-4, weight_decay: float = 0.1,
                    clip: float = 1.0, global_batch: int = 8, seq: int = 32,
                    seed: int = 0, schedule_sink=None):
    """Whole-train-step bench body for one cluster: forward + backward +
    gradient bridge + optimizer, as a ``repro.bench`` case.

    Returns ``(body, in_specs, out_specs, make_args, elems)`` with the
    state tree FLATTENED into separate top-level args (``BenchCase.compile``
    shards one plain ``PartitionSpec`` per arg) and ``elems`` = the model's
    global parameter element count (the family's recorded message size).
    Everything runs fp32 (the bench artifact's recorded dtype); the body
    returns three replicated f32 scalars — loss, grad norm, and a parameter
    checksum that keeps the whole optimizer update alive under DCE.

    ``unroll`` feeds the unit scan: the ``step_time`` family's eager
    baseline unrolls all units (``unroll=cfg.n_units``) so it differs from
    the prefetch schedule ONLY in gather placement — scan-vs-unroll is an
    orthogonal code-layout effect the family deliberately holds constant.

    With the ``stepgraph`` opt the scalar stats and the per-leaf gradient
    reductions are recorded into one ``CollectiveGraph`` and run as the
    bucketed/reordered schedule; ``schedule_sink`` (a list) receives the
    schedule ``report()`` dict at trace time for inspection.
    """
    ctx = cluster_ctx(vc, opts=opts)
    sizes = dict(zip(vc.axis_names, vc.axis_shapes))
    data = 1
    for a in ctx.fsdp_axes:
        data *= sizes[a]
    model = build(cfg, ctx, data=data)
    defs = model.defs
    pspecs = model.param_specs(tp_axis=ctx.tp_axis,
                               fsdp_axis=ctx.fsdp_axes[0]
                               if ctx.fsdp_axes else None)
    state_specs = {"params": pspecs, "m": pspecs, "v": pspecs, "step": P()}
    bspec = P(ctx.dp_axes)
    meta_leaves = jax.tree.leaves(defs,
                                  is_leaf=lambda x: isinstance(x, PMeta))
    world = Communicator.from_cluster(vc)
    node = world.split_type_shared()

    from repro.models.transformer import _loss  # local-body entry

    def step(state, batch):
        params = state["params"]

        def lf(p):
            return _loss(cfg, ctx, defs, p, {"tokens": batch},
                         unroll=unroll)

        (loss_sum, cnt), grads = jax.value_and_grad(lf, has_aux=True)(params)
        # scalar stats: pinned to the flat scheme so the step's lowering is
        # one fixed program per topology (auto would couple the bench body
        # to the tuning table's per-topology winner, and scatter-based
        # winners cannot scatter a 0-d operand anyway)
        if ctx.stepgraph:
            rec = world.record()
            rl = rec.allreduce(loss_sum, axes=world.axes, scheme="naive",
                               key="loss")
            rc = rec.allreduce(cnt, axes=world.axes, scheme="naive",
                               key="cnt")
            grads = ctx.reduce_grads(grads, meta_leaves, recorder=rec)
            res = rec.run()
            if schedule_sink is not None:
                schedule_sink.append(res.report())
            loss_g, cnt_g = res[rl], res[rc]
            grads = res.resolve(grads)
        else:
            loss_g = world.allreduce(loss_sum, scheme="naive")
            cnt_g = world.allreduce(cnt, scheme="naive")
            grads = ctx.reduce_grads(grads, meta_leaves)
        grads = jax.tree.map(lambda g: g / cnt_g, grads)
        gsq = ctx.grad_sq_norm(grads, meta_leaves, node, world,
                               scheme="naive")
        gnorm = jnp.sqrt(gsq)
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        new_params, _, _ = adamw_update(
            params, grads, state["m"], state["v"], state["step"] + 1,
            lr=lr, weight_decay=weight_decay)
        csum = jnp.float32(0.0)
        for leaf in jax.tree.leaves(new_params):
            csum += jnp.sum(leaf.astype(jnp.float32))
        csum = world.allreduce(csum, scheme="naive")
        return loss_g / cnt_g, gnorm, csum

    spec_leaves, spec_tree = jax.tree.flatten(
        state_specs, is_leaf=lambda x: isinstance(x, P))

    def body(*args):
        state = jax.tree.unflatten(spec_tree, args[:-1])
        return step(state, args[-1])

    in_specs = tuple(spec_leaves) + (bspec,)
    out_specs = (P(), P(), P())

    def make_args():
        params = model.init_params(seed)
        m, v = adamw_init(params)
        state = {"params": params, "m": m, "v": v,
                 "step": jnp.zeros((), jnp.int32)}
        # deterministic token stream (Knuth multiplicative hash of position)
        toks = (jnp.arange(global_batch * (seq + 1), dtype=jnp.uint32)
                * jnp.uint32(2654435761)) % jnp.uint32(cfg.vocab)
        tokens = toks.astype(jnp.int32).reshape(global_batch, seq + 1)
        return tuple(jax.tree.flatten(state)[0]) + (tokens,)

    pshapes = jax.eval_shape(model.init_params)
    elems = 0
    for leaf in jax.tree.leaves(pshapes):
        n = 1
        for d in leaf.shape:
            n *= d
        elems += n
    return body, in_specs, out_specs, make_args, elems


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeStepBundle:
    prefill: Any
    decode: Any
    param_specs: Any         # serve layout
    prefill_param_specs: Any  # train layout (prefill runs in it)
    cache_spec: Any
    batch_spec: Any
    model: Model
    s_max: int
    b_loc: int


def _dp_tuple(topo: MeshTopology) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in topo.axis_sizes)


def make_serve_steps(cfg: ModelConfig, topo: MeshTopology, mesh, *,
                     mode: str = "hier", global_batch: int, s_max: int,
                     unroll: int = 1, opts=(),
                     compute_dtype=jnp.bfloat16) -> ServeStepBundle:
    model = build_model(cfg, topo, mode, compute_dtype, opts)
    dp = _dp_tuple(topo)
    n_dp = 1
    for a in dp:
        n_dp *= topo.size(a)
    # small batches (long_500k: B=1) replicate over dp instead of sharding
    shard_batch = global_batch % n_dp == 0 and global_batch >= n_dp
    dp_b = dp if shard_batch else ()
    b_loc = global_batch // n_dp if shard_batch else global_batch
    bspec = batch_specs(cfg, topo)
    if not shard_batch:
        bspec = jax.tree.map(lambda s: P(), bspec,
                             is_leaf=lambda x: isinstance(x, P))
    pspecs_serve = model.param_specs(serve=True)
    pspecs_train = model.param_specs(serve=False)

    # decode cache: device-major layout (DP, TP, *local_shape)
    local_cache = jax.eval_shape(lambda: model.cache_init(b_loc, s_max))
    cache_spec = jax.tree.map(
        lambda _: P(dp_b if dp_b else None, "model"), local_cache)

    def prefill_body(params, batch):
        cache, logits = model.prefill_fn(params, batch, s_max, unroll=unroll)
        cache = jax.tree.map(lambda a: a[None, None], cache)
        return cache, logits

    def decode_body(params, cache, token, pos):
        cache = jax.tree.map(lambda a: a[0, 0], cache)
        new_cache, logits = model.decode_fn(params, cache, token, pos,
                                            unroll=unroll)
        new_cache = jax.tree.map(lambda a: a[None, None], new_cache)
        return new_cache, logits

    tok_spec = P(dp_b) if dp_b else P()
    logit_spec = P(dp_b) if dp_b else P()
    prefill = shard_map(
        prefill_body, mesh=mesh, in_specs=(pspecs_train, bspec),
        out_specs=(cache_spec, logit_spec), check_vma=False)
    decode = shard_map(
        decode_body, mesh=mesh,
        in_specs=(pspecs_serve, cache_spec, tok_spec, P()),
        out_specs=(cache_spec, logit_spec), check_vma=False)
    return ServeStepBundle(prefill=prefill, decode=decode,
                           param_specs=pspecs_serve,
                           prefill_param_specs=pspecs_train,
                           cache_spec=cache_spec, batch_spec=bspec,
                           model=model, s_max=s_max, b_loc=b_loc)
