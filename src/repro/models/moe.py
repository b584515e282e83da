"""Mixture-of-Experts block: top-k routing over every expert.

Train/prefill (expert parallel, dropless).  Each chip holds ``E / ep``
experts of every layer: the expert dim of the weights is sharded over the
data-parallel axes ``ParallelCtx.expert_axes`` picks (``(pod, data)`` on a
2x2 host), and they are never gathered.  A chip routes its own tokens over
all E experts (float32 logits at ``Precision.HIGHEST``), then:

* **dispatch** — every token goes once to each chip of the expert group, in
  a fixed ``(ep, N, d)`` buffer through ``Communicator.alltoall`` (the
  scheme left to ``auto``: the paper's two-stage exchange), with the
  token's ids on that chip and gates beside it in a ``(ep, N, 2k)`` one;
* **experts** — every received slot is sorted by expert into a buffer of
  ``ep * N * k`` rows, which no routing overflows, and the gated products
  run over all of it as grouped matmuls (``lax.ragged_dot``); the slots not
  held here ride as zero-gate rows, so the work is the same whatever the
  routing, and rows move in and out of the buffer by gathers, forward and
  backward;
* **combine** — each output is weighted by its gate, a token's experts on
  this chip are summed here, the ``(ep, N, d)`` result returns through the
  alltoall and the source sums it over the chips.

No token is dropped.  With a tensor-parallel ("model") axis the experts are
first factored over it as ``(ep_tp, tp_ff) = MoESpec.ep_tp(tp)``: the
sequence-parallel tokens are gathered over tp, each tp rank routes them to
its own group of experts (its ffn slice of each) over the expert axes, and
ONE reduce-scatter sums the groups' and slices' partials and returns the
sequence-parallel layout.

Serve (decode): 1-token batches are tiny, so the capacity dispatch runs over
the pod-gathered token set with experts spread over (model x data) —
weights stay put, tokens move (see DESIGN.md §5).  Its dispatch is
argsort-based (gather tables, no one-hot einsum) so HLO FLOPs reflect real
expert compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm.primitives import scoped
from repro.models.layers import activation, rms_norm
from repro.models.parallel import ParallelCtx

def route(h: jax.Array, router_w: jax.Array, top_k: int):
    """h: (N, d) -> (idx (N,k) int32, gate (N,k) f32) — softmaxed over top-k
    (Qwen3/granite style norm_topk_prob)."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    vals, idx = lax.top_k(logits, top_k)
    gate = jax.nn.softmax(vals, axis=-1)
    return idx.astype(jnp.int32), gate


def dispatch_tables(idx: jax.Array, *, e0: int, n_local: int, capacity: int):
    """Build gather/scatter tables for the local expert group.

    idx: (N, k) global expert ids.  Returns
      table   (n_local, capacity): token index feeding each expert slot
              (N = dummy/empty),
      gates_sel (n_local, capacity): routing-slot index into idx/gate rows
              (for combine), -1 when empty.
    """
    N, k = idx.shape
    flat = idx.reshape(N * k)
    local = flat - e0
    key = jnp.where((local >= 0) & (local < n_local), local, n_local)
    order = jnp.argsort(key, stable=True)                  # (N*k,)
    skey = key[order]
    counts = jnp.bincount(key, length=n_local + 1)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N * k) - starts[skey]
    keep = (skey < n_local) & (pos < capacity)
    row = jnp.where(keep, skey, n_local)                   # clipped rows
    col = jnp.where(keep, pos, 0)
    tok = order // k
    table = jnp.full((n_local + 1, capacity), N, jnp.int32)
    table = table.at[row, col].set(jnp.where(keep, tok, N).astype(jnp.int32))
    slot = jnp.full((n_local + 1, capacity), -1, jnp.int32)
    slot = slot.at[row, col].set(jnp.where(keep, order, -1).astype(jnp.int32))
    return table[:n_local], slot[:n_local]


def expert_ffn(buf: jax.Array, w_in: jax.Array, w_out: jax.Array, act: str
               ) -> jax.Array:
    """buf: (E_loc, C, d); w_in: (E_loc, d, 2, dff_loc) — explicit gate/up
    axis so dff sharding never splits across the halves; w_out:
    (E_loc, dff_loc, d)."""
    u = jnp.einsum("ecd,edgf->ecgf", buf, w_in)
    a = activation(act, u[:, :, 0], u[:, :, 1])
    return jnp.einsum("ecf,efd->ecd", a, w_out)


def moe_block(x_sp: jax.Array, p: dict, meta: dict, ctx: ParallelCtx, cfg, *,
              serve: bool = False, stats: list | None = None) -> jax.Array:
    """x_sp: (B, T/tp, d) (train/prefill) or (B, 1, d) (serve).  ``stats``
    (train only), a list, gets this layer's routed-row count per expert
    from this chip's tokens, ``(E,)`` float32."""
    if serve:
        return _moe_serve(x_sp, p, meta, ctx, cfg)
    with jax.named_scope("moe"):
        return _moe_train(x_sp, p, meta, ctx, cfg, stats)


def _moe_train(x_sp, p, meta, ctx: ParallelCtx, cfg, stats):
    spec = cfg.moe
    E, k = spec.num_experts, spec.top_k
    ep_tp, tp_ff = spec.ep_tp(ctx.tp)
    e_grp = E // ep_tp                       # the experts of my tp group

    h = rms_norm(x_sp, ctx.gather_w(p["ln"], meta["ln"].fsdp_dim),
                 cfg.norm_eps)
    hg = ctx.ag_tokens(h)                                   # (B, T, d)
    B, T, d = hg.shape
    N = B * T
    tokens = hg.reshape(N, d)
    with jax.named_scope("router"):
        router = ctx.gather_w(p["router"], meta["router"].fsdp_dim)
        idx, gate = route(tokens, router, k)                # (N, k)
        if stats is not None:
            stats.append(jnp.zeros(E, jnp.float32).at[idx.reshape(-1)]
                         .add(1.0))

    # this chip's experts: stored (tp, e_grp, ...), local (1, n_loc, ...)
    w_in = ctx.gather_w(p["w_in"], meta["w_in"].fsdp_dim)[0]
    w_out = ctx.gather_w(p["w_out"], meta["w_out"].fsdp_dim)[0]
    n_loc = w_in.shape[0]
    ep = e_grp // n_loc                      # chips the group spreads over
    axes = meta["w_in"].ep_axes
    comm = ctx.axes_comm(axes) if axes else None

    with jax.named_scope("dispatch"):
        grp, _ = ctx.tp_group_rank(tp_ff)
        local = idx - grp * e_grp                           # id in my group
        dest = jnp.where((local >= 0) & (local < e_grp), local // n_loc, ep)
        onto = dest[None] == jnp.arange(ep)[:, None, None]  # (ep, N, k)
        ids = jnp.where(onto, local[None] % n_loc, n_loc)   # n_loc: none
        route_buf = jnp.concatenate(
            [ids.astype(jnp.float32), jnp.where(onto, gate[None], 0.0)], -1)
        rows = jnp.broadcast_to(tokens, (ep, N, d))
        if comm is not None:
            rows = comm.alltoall(rows)
            route_buf = comm.alltoall(route_buf)
        ids = lax.stop_gradient(route_buf[..., :k]).astype(jnp.int32)
        ids, gates = ids.reshape(-1), route_buf[..., k:].reshape(-1)

    y = experts(rows.reshape(ep * N, d), ids, gates, w_in, w_out, k=k,
                act=cfg.act)
    with jax.named_scope("combine"):
        y = y.reshape(ep, N, d)
        if comm is not None:
            y = comm.alltoall(y)
        y = jnp.sum(y, axis=0).reshape(B, T, d)
        return x_sp + ctx.rs_tokens(y)   # sums tp groups + ffn slices, SP


def experts(rows, ids, gates, w_in, w_out, *, k: int, act: str):
    """The gated expert products of the received rows, summed per row.

    rows: (R, d), each with ``k`` (local expert id, gate) slots in ``ids``
    and ``gates`` (R * k,), id ``n_loc`` where the slot is not this chip's.
    Every slot is sorted by expert into a buffer of R * k rows, so no
    routing overflows it, and the gated products run over the whole buffer
    as grouped matmuls (``lax.ragged_dot``), the slots not held here as
    zero-gate rows of the last group: a step's work does not change with
    the routing.  Rows reach the buffer, and its outputs their rows, by
    gathers, forward and backward."""
    n_loc, d, n_ff = w_in.shape[0], w_in.shape[1], w_in.shape[-1]
    with jax.named_scope("experts"):
        order = jnp.argsort(ids, stable=True).astype(jnp.int32)
        # each slot's row of the buffer
        at = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.size, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.bincount(ids, length=n_loc + 1).astype(jnp.int32)
        sizes = sizes[:n_loc].at[-1].add(sizes[n_loc])
        held = jnp.take(ids, order) < n_loc
        g = jnp.where(held, jnp.take(gates, order), 0.0)
        xs = _permute(rows, order, at, k)
        u = lax.ragged_dot(xs, w_in.reshape(n_loc, d, 2 * n_ff), sizes)
        a = activation(act, u[:, :n_ff], u[:, n_ff:])
        out = lax.ragged_dot(a, w_out, sizes) * g[:, None].astype(u.dtype)
        return _unpermute(out, order, at, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _permute(rows, order, at, k: int):
    """(R, d) rows -> (R * k, d) buffer: row ``order[i] // k`` at ``i``.
    Its transpose is :func:`_unpermute`, a gather too (autodiff's would
    scatter-add)."""
    return jnp.take(rows, order // k, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _unpermute(out, order, at, k: int):
    """(R * k, d) buffer -> (R, d): each row's sum over its ``k`` slots'
    buffer rows ``at``.  Its transpose is :func:`_permute`."""
    return jnp.take(out, at, axis=0).reshape(-1, k, out.shape[1]).sum(1)


_permute.defvjp(
    lambda rows, order, at, k: (_permute(rows, order, at, k), (order, at)),
    lambda k, res, d_xs: (_unpermute(d_xs, *res, k), None, None))
_unpermute.defvjp(
    lambda out, order, at, k: (_unpermute(out, order, at, k), (order, at)),
    lambda k, res, dy: (_permute(dy, *res, k), None, None))


def _moe_serve(x_sp, p, meta, ctx: ParallelCtx, cfg):
    """Decode: capacity dispatch over the pod-gathered tokens, experts held
    (model x data)-sharded, combined by psum."""
    spec = cfg.moe
    eps = cfg.norm_eps
    E, k = spec.num_experts, spec.top_k
    ep, tp_ff = spec.ep_tp(ctx.tp)
    n_local = E // ep

    h = rms_norm(x_sp, ctx.gather_w(p["ln"], meta["ln"].fsdp_dim), eps)
    # tokens move, weights stay: gather the pod's token set over the data
    # axis (hier; expert dff is stored data-sharded), or keep local (naive;
    # weights fully replicated).
    if ctx.mode == "hier" and ctx.fsdp_axes:
        hg = scoped(lax.all_gather,  # raw-collective: expert dispatch
                    h, ctx.fsdp_axes, axis=0, tiled=True)
    else:
        hg = h
    B, T, d = hg.shape
    tokens = hg.reshape(B * T, d)
    N = B * T

    router = ctx.gather_w(p["router"], meta["router"].fsdp_dim)  # (d, E)
    idx, gate = route(tokens, router, k)

    ep_idx, _ = ctx.tp_group_rank(tp_ff)                    # outer=ep, inner=ff
    e0 = ep_idx * n_local
    capacity = int(N * k / E * spec.capacity_factor) + 1
    table, slot = dispatch_tables(idx, e0=e0, n_local=n_local,
                                  capacity=capacity)

    tok_pad = jnp.concatenate([tokens, jnp.zeros((1, d), tokens.dtype)])
    buf = jnp.take(tok_pad, table, axis=0)                  # (E_loc, C, d)

    # local expert weights: stored (tp, E_loc, d, 2*dff/tp_ff) sharded on
    # dim0 -> local (1, E_loc, d, n_in)
    w_in = ctx.gather_w(p["w_in"], meta["w_in"].fsdp_dim)[0]
    w_out = ctx.gather_w(p["w_out"], meta["w_out"].fsdp_dim)[0]
    out_buf = expert_ffn(buf, w_in, w_out, cfg.act)         # (E_loc, C, d)

    gflat = jnp.concatenate([gate.reshape(N * k),
                             jnp.zeros(1, gate.dtype)])
    gsel = jnp.where(slot >= 0, gflat[jnp.clip(slot, 0)],
                     0.0).astype(out_buf.dtype)
    out_buf = out_buf * gsel[..., None]

    y = jnp.zeros((N + 1, d), out_buf.dtype)
    y = y.at[table.reshape(-1)].add(out_buf.reshape(-1, d))
    y = y[:N].reshape(B, T, d)
    if ctx.mode == "hier" and ctx.fsdp_axes:
        # raw-collective: expert-dispatch fast path, both arms
        axes = ((ctx.tp_axis,) if ctx.tp_axis else ()) \
            + tuple(ctx.fsdp_axes)
        y = scoped(lax.psum, y, axes)  # raw-collective: above
        b_loc = x_sp.shape[0]
        r = lax.axis_index(ctx.fsdp_axes[0])
        y = lax.dynamic_slice_in_dim(y, r * b_loc, b_loc, 0)
    else:
        y = ctx.psum_tp(y)
    return x_sp + y


def aux_load_balance_loss(idx: jax.Array, gate: jax.Array, E: int
                          ) -> jax.Array:
    """Switch-style auxiliary loss (fraction-dispatched x mean-gate)."""
    N, k = idx.shape
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (N, k, E)
    frac = jnp.mean(jnp.sum(onehot, axis=1), axis=0)        # (E,)
    prob = jnp.mean(jnp.sum(onehot * gate[..., None], axis=1), axis=0)
    return E * jnp.sum(frac * prob)
