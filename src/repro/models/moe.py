"""Mixture-of-Experts block: top-k routing + capacity dispatch, EP-aware.

Train/prefill: experts sharded over the tp ("model") axis, factored as
(ep, tp_ff) = MoESpec.ep_tp(tp) so non-divisible expert counts (granite: 40
experts over 16 chips -> ep=8, tp_ff=2) still map exactly.  Tokens are the
sequence-parallel gather (all chips of a tp group see the same tokens), each
chip computes its local experts' capacity buffers, and ONE reduce-scatter
combines expert-parallel partial sums, ffn-TP partial sums and the SP return.

Serve (decode): 1-token batches are tiny, so the same dispatch runs over the
pod-gathered token set with experts spread over (model x data) — weights stay
put, tokens move (see DESIGN.md §5).

Dispatch is argsort-based (gather tables, no one-hot einsum) so HLO FLOPs
reflect real expert compute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm.primitives import scoped
from repro.models.layers import activation, rms_norm
from repro.models.parallel import ParallelCtx


def route(h: jax.Array, router_w: jax.Array, top_k: int):
    """h: (N, d) -> (idx (N,k) int32, gate (N,k) f32) — softmaxed over top-k
    (Qwen3/granite style norm_topk_prob)."""
    logits = h.astype(jnp.float32) @ router_w.astype(jnp.float32)
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
              serve: bool = False) -> jax.Array:
    """x_sp: (B, T/tp, d) (train/prefill) or (B, 1, d) (serve)."""
    spec = cfg.moe
    eps = cfg.norm_eps
    E, k = spec.num_experts, spec.top_k
    ep, tp_ff = spec.ep_tp(ctx.tp)
    n_local = E // ep

    h = rms_norm(x_sp, ctx.gather_w(p["ln"], meta["ln"].fsdp_dim), eps)
    if serve:
        # tokens move, weights stay: gather the pod's token set over the
        # data axis (hier; expert dff is stored data-sharded), or keep local
        # (naive; weights fully replicated).
        if ctx.mode == "hier" and ctx.fsdp_axes:
            hg = scoped(lax.all_gather,  # raw-collective: expert dispatch
                        h, ctx.fsdp_axes, axis=0, tiled=True)
        else:
            hg = h
    else:
        hg = ctx.ag_tokens(h)                               # (B, T, d)
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
    if serve:
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
    return x_sp + ctx.rs_tokens(y)  # combines EP + ffn-TP partials + SP


def aux_load_balance_loss(idx: jax.Array, gate: jax.Array, E: int
                          ) -> jax.Array:
    """Switch-style auxiliary loss (fraction-dispatched x mean-gate)."""
    N, k = idx.shape
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (N, k, E)
    frac = jnp.mean(jnp.sum(onehot, axis=1), axis=0)        # (E,)
    prob = jnp.mean(jnp.sum(onehot * gate[..., None], axis=1), axis=0)
    return E * jnp.sum(frac * prob)
