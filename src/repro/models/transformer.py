"""Decoder assembly: pattern-unit scan, train/prefill/decode entry points.

The model is a stack of *pattern units* (cfg.pattern repeated cfg.n_units
times, plus an unrolled remainder).  Unit params are stacked on a leading dim
and the stack is traversed with ``lax.scan`` (+ jax.checkpoint remat), so
compiles stay fast at 94 layers; the roofline corrects loop-body FLOP
undercounts via unroll-extrapolation + the analytic notes in ``cost_notes``.

All functions are shard_map bodies: arrays are LOCAL shards, collective
semantics live in ParallelCtx / the block implementations.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import meta as M
from repro.models.attention import (attn_block, attn_flops, cache_write,
                                    decode_attention)
from repro.models.layers import (decode_logits, embed, ffn, ffn_decode,
                                 rms_norm, sinusoidal_pe, unembed_xent)
from repro.models.moe import moe_block
from repro.models.parallel import (ParallelCtx, ParamGroup, prefetch_walk,
                                   tp_slice)
from repro.models.rglru import rglru_block, rglru_state_init
from repro.models.xlstm import (mlstm_block, mlstm_state_init, slstm_block,
                                slstm_scan_flops, slstm_state_init)

KV_BLOCK = 1024   # flash attention KV block (roofline notes depend on it)
XENT_CHUNK = 512
MLSTM_CHUNK = 128


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    ctx: ParallelCtx
    defs: Any            # PMeta tree (train) — serve variants built on demand
    serve_defs: Any

    # ---- params ------------------------------------------------------------
    def init_params(self, seed: int = 0):
        return M.init_params(self.defs, self.cfg, seed)

    def param_specs(self, *, serve: bool = False, tp_axis="model",
                    fsdp_axis="data"):
        defs = self.serve_defs if serve else self.defs
        return M.param_specs(defs, self.cfg, tp_axis=tp_axis,
                             fsdp_axis=fsdp_axis)

    def abstract_params(self, specs, *, serve: bool = False):
        defs = self.serve_defs if serve else self.defs
        return M.abstract_params(defs, self.cfg, specs)

    # ---- entry points (shard_map bodies) ------------------------------------
    def loss_fn(self, params, batch):
        return _loss(self.cfg, self.ctx, self.defs, params, batch)

    def prefill_fn(self, params, batch, s_max: int, *, unroll: int = 1):
        # prefill is big-token work: it runs in the TRAIN parallel layout
        return _prefill(self.cfg, self.ctx, self.defs, params, batch, s_max,
                        unroll=unroll)

    def decode_fn(self, params, cache, token, pos, *, unroll: int = 1):
        return _decode(self.cfg, self.ctx, self.serve_defs, params, cache,
                       token, pos, unroll=unroll)

    def cache_init(self, B_loc: int, s_max: int):
        return _cache_init(self.cfg, self.ctx, B_loc, s_max)

    def cost_notes(self, *, kind: str, B: int, T: int) -> dict[str, float]:
        return _cost_notes(self.cfg, kind=kind, B=B, T=T)


def build(cfg: ModelConfig, ctx: ParallelCtx, data: int = 1) -> Model:
    ep_axes = ()
    if cfg.moe:
        ep_axes = ctx.expert_axes(cfg.moe.num_experts
                                  // cfg.moe.ep_tp(ctx.tp)[0])
    defs = M.model_defs(cfg, ctx.tp, data, ctx.mode, serve=False,
                        opts=ctx.opts, ep_axes=ep_axes)
    serve_defs = M.model_defs(cfg, ctx.tp, data, ctx.mode, serve=True,
                              opts=ctx.opts)
    return Model(cfg, ctx, defs, serve_defs)


# ---------------------------------------------------------------------------
# Blocks dispatch
# ---------------------------------------------------------------------------

def _mix(kind: str, x, p, mt, ctx, cfg, *, serve=False, stats=None):
    """Channel-mixing half of attn/local/rglru blocks.  ``stats`` (a list)
    collects each expert layer's routed-row count per expert."""
    if cfg.moe:
        return moe_block(x, p["moe"], mt["moe"], ctx, cfg, serve=serve,
                         stats=stats)
    if not cfg.d_ff:
        return x
    f = ffn_decode if serve else ffn
    return f(x, p["ffn"], mt["ffn"], ctx, act=cfg.act, eps=cfg.norm_eps)


def _block_train(kind: str, x, p, mt, ctx, cfg, *, return_state=False,
                 stats=None):
    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else None
        mode = M.attn_mode_for(cfg, ctx.tp)
        if return_state:
            x, kv = attn_block(x, p["attn"], mt["attn"], ctx, cfg, mode=mode,
                               window=window, return_kv=True)
        else:
            x = attn_block(x, p["attn"], mt["attn"], ctx, cfg, mode=mode,
                           window=window)
        x = _mix(kind, x, p, mt, ctx, cfg, stats=stats)
        return (x, {"k": kv[0], "v": kv[1]}) if return_state else x
    if kind == "mlstm":
        chunk = MLSTM_CHUNK
        for o in ctx.opts:   # §Perf knob: --opts mchunk=256
            if o.startswith("mchunk="):
                chunk = int(o[7:])
        out = mlstm_block(x, p["mlstm"], mt["mlstm"], ctx, cfg,
                          chunk=chunk, return_state=return_state)
        return out
    if kind == "slstm":
        out = slstm_block(x, p["slstm"], mt["slstm"], ctx, cfg,
                          return_state=return_state)
        return out
    if kind == "rglru":
        out = rglru_block(x, p["rglru"], mt["rglru"], ctx, cfg,
                          return_state=return_state)
        if return_state:
            x, st = out
            x = _mix(kind, x, p, mt, ctx, cfg)
            return x, st
        x = _mix(kind, out, p, mt, ctx, cfg, stats=stats)
        return x
    raise ValueError(kind)


def _decode_attn_2d(x, p, mt, state, ctx, cfg, *, pos, window):
    """2D decode attention (EXPERIMENTS.md §Perf): the tp axis is factored
    into g_h head groups x g_s seq groups.  Attention weights stay sharded
    by head group (no per-step FSDP gather); the cache chunk is S/g_s per
    chip; partial softmax merges within the head group's g_s chips."""
    import math as _math
    from repro.models.attention import _kv_head_map
    if jnp.ndim(pos) != 0:
        raise ValueError("decode2d decode attention needs a scalar pos; "
                         "per-slot position vectors (continuous batching) "
                         "are only supported on the 1D decode path")
    g_h, g_s = M.decode2d_groups(cfg, ctx.tp)
    H, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    Hg, kvg = H // g_h, kv // g_h
    ring = window is not None
    eps = cfg.norm_eps
    B = x.shape[0]

    h = rms_norm(x, ctx.gather_w(p["attn"]["ln"],
                                 mt["attn"]["ln"].fsdp_dim), eps)
    wq = p["attn"]["wq"][0].astype(ctx.compute_dtype)   # (d, Hg*hd)
    wkv = p["attn"]["wkv"][0].astype(ctx.compute_dtype)  # (d, 2, kvg*hd)
    wo = p["attn"]["wo"][0].astype(ctx.compute_dtype)   # (Hg*hd, d)
    q = (h @ wq).reshape(B, 1, Hg, hd)
    kvp = jnp.einsum("btd,dgk->btgk", h, wkv).reshape(B, 1, 2, kvg, hd)
    k_new, v_new = kvp[:, :, 0], kvp[:, :, 1]
    if cfg.qk_norm:
        q = rms_norm(q, ctx.gather_w(p["attn"]["q_norm"],
                                     mt["attn"]["q_norm"].fsdp_dim), eps)
        k_new = rms_norm(k_new, ctx.gather_w(
            p["attn"]["k_norm"], mt["attn"]["k_norm"].fsdp_dim), eps)
    if cfg.pos == "rope":
        from repro.models.layers import rope
        rdt = ctx.compute_dtype if ctx.has("bf16_rope") else None
        pos_arr = jnp.full((1,), pos)
        q = rope(q, pos_arr, cfg.rope_theta, rdt)
        k_new = rope(k_new, pos_arr, cfg.rope_theta, rdt)

    # cache write: slot owner within my head group's seq chips
    kc_, vc_ = state["k"], state["v"]                   # (B, S/g_s, kvg, hd)
    S_loc = kc_.shape[1]
    gpos = pos % window if window is not None else pos
    s_idx = ctx.tp_rank % g_s
    owner = gpos // S_loc
    local = gpos - owner * S_loc
    hit = (jnp.arange(S_loc) == local) & (s_idx == owner)
    kc_ = jnp.where(hit[None, :, None, None], k_new.astype(kc_.dtype), kc_)
    vc_ = jnp.where(hit[None, :, None, None], v_new.astype(vc_.dtype), vc_)

    # partial attention over my S/g_s chunk
    base = s_idx * S_loc
    slot = base + jnp.arange(S_loc)
    if ring:
        W = window
        gidx = pos - ((pos - slot) % W)
        valid = (gidx >= 0) & (gidx <= pos) & (pos - gidx < W)
    else:
        valid = slot <= pos
    kvmap = _kv_head_map(Hg, 0, Hg, kvg)
    kq = jnp.take(kc_, kvmap, axis=2).astype(jnp.float32)
    vq = jnp.take(vc_, kvmap, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk",
                   q.astype(jnp.float32) / _math.sqrt(hd), kq)
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    m_loc = jnp.max(s, axis=-1)
    mg = ctx.group_all_gather(m_loc[None], group=g_s, dim=0)
    m_all = jnp.max(mg, axis=0)
    pexp = jnp.exp(s - m_all[..., None])
    l = ctx.group_psum(jnp.sum(pexp, axis=-1), group=g_s)
    o = ctx.group_psum(jnp.einsum("bhqk,bkhd->bhqd", pexp, vq), group=g_s)
    o = (o / jnp.maximum(l[..., None], 1e-30)).transpose(0, 2, 1, 3)
    # out proj on my head group; only the seq-primary contributes to the
    # cross-head-group psum (others are duplicates)
    y = (o.reshape(B, 1, Hg * hd).astype(ctx.compute_dtype) @ wo)
    y = jnp.where(s_idx == 0, y, jnp.zeros_like(y))
    y = ctx.psum_tp(y)
    x = x + y
    return x, {"k": kc_, "v": vc_}


def _block_decode(kind: str, x, p, mt, state, ctx, cfg, *, pos):
    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else None
        if ctx.has("decode2d") and ctx.tp_axis \
                and M.decode2d_groups(cfg, ctx.tp):
            x, st = _decode_attn_2d(x, p, mt, state, ctx, cfg, pos=pos,
                                    window=window)
            x = _mix(kind, x, p, mt, ctx, cfg, serve=True)
            return x, st
        ring = window is not None
        H, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
        h = rms_norm(x, ctx.gather_w(p["attn"]["ln"],
                                     mt["attn"]["ln"].fsdp_dim), cfg.norm_eps)
        wq = ctx.gather_w(p["attn"]["wq"], mt["attn"]["wq"].fsdp_dim)
        wkv = ctx.gather_w(p["attn"]["wkv"], mt["attn"]["wkv"].fsdp_dim)
        wo = ctx.gather_w(p["attn"]["wo"], mt["attn"]["wo"].fsdp_dim)
        B = x.shape[0]
        q = (h @ wq).reshape(B, 1, H, hd)
        kvp = jnp.einsum("btd,dgk->btgk", h, wkv).reshape(B, 1, 2, kv, hd)
        k_new, v_new = kvp[:, :, 0], kvp[:, :, 1]
        if cfg.qk_norm:
            q = rms_norm(q, ctx.gather_w(p["attn"]["q_norm"],
                                         mt["attn"]["q_norm"].fsdp_dim),
                         cfg.norm_eps)
            k_new = rms_norm(k_new, ctx.gather_w(
                p["attn"]["k_norm"], mt["attn"]["k_norm"].fsdp_dim),
                cfg.norm_eps)
        if cfg.pos == "rope":
            from repro.models.layers import rope_decode
            rdt = ctx.compute_dtype if ctx.has("bf16_rope") else None
            q = rope_decode(q, pos, cfg.rope_theta, rdt)
            k_new = rope_decode(k_new, pos, cfg.rope_theta, rdt)
        kc = cache_write(state["k"], k_new, ctx, pos=pos, window=window)
        vc = cache_write(state["v"], v_new, ctx, pos=pos, window=window)
        o = decode_attention(q, kc, vc, ctx, pos=pos, H=H, window=window,
                             ring=ring)
        # q/kv/o replicated over tp (decode_attention merged with psums), so
        # y is identical on every chip — plain residual add, no collective.
        y = o.reshape(B, 1, H * hd) @ wo
        x = x + y
        x = _mix(kind, x, p, mt, ctx, cfg, serve=True)
        return x, {"k": kc, "v": vc}
    if kind == "mlstm":
        x, st = mlstm_block(x, p["mlstm"], mt["mlstm"], ctx, cfg,
                            state=state, decode=True)
        return x, st
    if kind == "slstm":
        x, st = slstm_block(x, p["slstm"], mt["slstm"], ctx, cfg,
                            state=state, decode=True)
        return x, st
    if kind == "rglru":
        x, st = rglru_block(x, p["rglru"], mt["rglru"], ctx, cfg,
                            state=state, decode=True)
        x = _mix(kind, x, p, mt, ctx, cfg, serve=True)
        return x, st
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Embedding / loss glue
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def _embed_sp(cfg, ctx, defs, params, batch, *, T: int):
    """Build the sequence-parallel input embedding (B, T/tp, d) plus FULL
    (labels, mask) of shape (B, T) — the streamed loss consumes full-T
    labels (see unembed_xent)."""
    tp, rank = ctx.tp, ctx.tp_rank
    T_loc = T // tp
    t0 = rank * T_loc if ctx.tp_axis else 0
    pos_loc = t0 + jnp.arange(T_loc)

    if cfg.frontend == "encodec":
        frames = batch["frames"]                            # (B, T, d_f)
        fr_loc = tp_slice(frames, rank, tp, 1) if ctx.tp_axis else frames
        w_fe = ctx.gather_w(params["frontend"], defs["frontend"].fsdp_dim)
        x = fr_loc.astype(ctx.compute_dtype) @ w_fe
        labels = batch["labels"]
        mask = jnp.ones_like(labels, jnp.float32)
    else:
        tokens = batch["tokens"]                            # (B, T+1)
        ids = tokens[:, :T]
        labels = tokens[:, 1:T + 1]
        emb = ctx.gather_w(params["embed"], defs["embed"].fsdp_dim)
        x = embed(ids, emb, ctx, sp=ctx.tp_axis is not None)
        mask = jnp.ones_like(labels, jnp.float32)
        if cfg.frontend == "vit":
            patches = batch["patches"]                      # (B, P, d_f)
            w_fe = ctx.gather_w(params["frontend"], defs["frontend"].fsdp_dim)
            pe = patches.astype(ctx.compute_dtype) @ w_fe   # (B, P, d)
            P_ = cfg.n_prefix
            idx = jnp.clip(pos_loc, 0, P_ - 1)
            pex = jnp.take(pe, idx, axis=1)
            is_patch = (pos_loc < P_)[None, :, None]
            x = jnp.where(is_patch, pex, x)
            mask = mask * ((jnp.arange(T) + 1) >= P_)[None, :]
    if cfg.tie_embeddings:  # gemma-style input scaling
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.pos == "sinusoidal":
        x = x + sinusoidal_pe(pos_loc, cfg.d_model)[None].astype(x.dtype)
    return x, labels, mask


def _unembed_weight(cfg, ctx, defs, params):
    if cfg.tie_embeddings:
        w = ctx.gather_w(params["embed"], defs["embed"].fsdp_dim)
        return w.T                                          # (d, V/tp)
    return ctx.gather_w(params["unembed"], defs["unembed"].fsdp_dim)


# ---------------------------------------------------------------------------
# Train loss
# ---------------------------------------------------------------------------

def _scan_units(cfg, ctx, defs, params, x, *, collect_state=False,
                unroll: int = 1):
    kinds = cfg.pattern

    def unit(x, pu):
        states, loads = {}, []
        for i, k in enumerate(kinds):
            key = f"b{i}"
            if collect_state:
                x, st = _block_train(k, x, pu[key], defs["units"][key], ctx,
                                     cfg, return_state=True)
                states[key] = st
            else:
                x = _block_train(k, x, pu[key], defs["units"][key], ctx, cfg,
                                 stats=loads)
        if collect_state:
            return x, states
        return x, (jnp.stack(loads) if loads else None)

    if collect_state:
        def body(x, pu):
            x, st = unit(x, pu)
            return x, st
        x, states = lax.scan(body, x, params["units"], unroll=unroll)
        return x, states

    if ctx.has("save_ag"):
        # §Perf: keep collective outputs across the bwd — the remat
        # recompute then skips every re-gather (trades footprint for
        # collective+memory traffic).
        policy = jax.checkpoint_policies.save_only_these_names("ag_out")
        remat = lambda f: jax.checkpoint(f, policy=policy)  # noqa: E731
    else:
        remat = jax.checkpoint

    budget = ctx.prefetch
    if budget > 0:
        # Async prefetch: an unrolled walk over per-unit ParamGroups — layer
        # k+1's FSDP window gathers are issued while layer k computes, at
        # most `budget` groups unsharded at once.  The unit body runs with
        # fsdp_axes cleared (its params arrive already full), which also
        # keeps the gathers OUTSIDE the remat region: the bwd recompute
        # reuses the unsharded copy instead of re-gathering.
        inner = dataclasses.replace(ctx, fsdp_axes=())

        def unit_full(x, pu):
            for i, k in enumerate(kinds):
                x = _block_train(k, x, pu[f"b{i}"], defs["units"][f"b{i}"],
                                 inner, cfg)
            return x
        unit_f = remat(unit_full)
        groups = [ParamGroup(ctx,
                             jax.tree.map(lambda u, i=i: u[i],
                                          params["units"]),
                             defs["units"])
                  for i in range(cfg.n_units)]
        x = prefetch_walk(groups, lambda c, _k, full: unit_f(c, full), x,
                          budget)
        return x, None

    # per unit: x, and each expert layer's routed-row count per expert
    return lax.scan(remat(unit), x, params["units"], unroll=unroll)


def _rem_blocks(cfg, ctx, defs, params, x, *, collect_state=False, pos=None,
                cache=None, decode=False, stats=None):
    states = {}
    for i, k in enumerate(cfg.remainder_kinds):
        key = f"r{i}"
        if decode:
            x, st = _block_decode(k, x, params["rem"][key], defs["rem"][key],
                                  cache[key], ctx, cfg, pos=pos)
            states[key] = st
        elif collect_state:
            x, st = _block_train(k, x, params["rem"][key], defs["rem"][key],
                                 ctx, cfg, return_state=True)
            states[key] = st
        else:
            x = _block_train(k, x, params["rem"][key], defs["rem"][key], ctx,
                             cfg, stats=stats)
    return x, states


def _loss(cfg, ctx, defs, params, batch, *, unroll: int = 1,
          stats: bool = False):
    """Returns (loss_sum, token_count) — local partials; caller reduces.
    With ``stats``, also each expert layer's routed-row count per expert
    from this chip's tokens, ``(layers, num_experts)`` (``None`` for a
    model without experts)."""
    T = (batch["frames"].shape[1] if cfg.frontend == "encodec"
         else batch["tokens"].shape[1] - 1)
    x, labels, mask = _embed_sp(cfg, ctx, defs, params, batch, T=T)
    x, loads = _scan_units(cfg, ctx, defs, params, x, unroll=unroll)
    rem_loads = []
    x, _ = _rem_blocks(cfg, ctx, defs, params, x, stats=rem_loads)
    with jax.named_scope("head"):
        x = rms_norm(x, ctx.gather_w(params["final_ln"],
                                     defs["final_ln"].fsdp_dim), cfg.norm_eps)
        w_un = _unembed_weight(cfg, ctx, defs, params)
        loss_sum, cnt = unembed_xent(x, labels, mask, w_un, ctx,
                                     chunk=XENT_CHUNK,
                                     softcap=cfg.logit_softcap)
    if not stats:
        return loss_sum, cnt
    parts = [r[None] for r in rem_loads]
    if loads is not None:
        parts = [loads.reshape(-1, loads.shape[-1])] + parts
    return loss_sum, cnt, (jnp.concatenate(parts) if parts else None)


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------

def _state_to_cache(cfg, ctx, st, T: int, s_max, kind, tdim: int = 1):
    """Re-layout prefill (k, v) T-chunks into the decode cache layout.

    Prefill chunks are sharded on the prompt length T; the decode cache is
    sharded on s_max (or the ring window).  Relayout = intra-pod gather (the
    shared-window read) + local slice.  Ring slots whose global position
    predates the prompt (T < window) are zero-filled — they are masked out
    of decode attention, but must not hold NaN (an out-of-bounds gather
    fill), because even a zero-weighted NaN poisons the softmax-weighted
    sum.  ``tdim``: time axis (2 for unit-stacked states).
    """
    if kind not in ("attn", "local"):
        return st
    window = cfg.window if kind == "local" else None
    tp, rank = max(ctx.tp, 1), ctx.tp_rank

    def relayout(a):                               # (..., T/tp, kv, hd)
        full = ctx.ag_tokens(a, dim=tdim)          # (..., T, kv, hd)
        if window is not None:
            W = min(window, s_max)
            # ring slot s holds position g = T-W + ((s - (T-W)) mod W)
            s = jnp.arange(W)
            g = T - W + ((s - (T - W)) % W)
            full = jnp.take(full, jnp.maximum(g, 0),
                            axis=tdim)             # (..., W, kv, hd)
            shape = [1] * full.ndim
            shape[tdim] = W
            full = jnp.where((g >= 0).reshape(shape), full,
                             jnp.zeros_like(full))
            S_loc = W // tp
            return lax.dynamic_slice_in_dim(full, rank * S_loc, S_loc, tdim)
        S_loc = s_max // tp
        pad = [(0, 0)] * full.ndim
        pad[tdim] = (0, s_max - T)
        full = jnp.pad(full, pad)
        return lax.dynamic_slice_in_dim(full, rank * S_loc, S_loc, tdim)

    return {"k": relayout(st["k"]), "v": relayout(st["v"])}


def _cache_init(cfg, ctx, B_loc, s_max):
    tp = max(ctx.tp, 1)
    d2d = (M.decode2d_groups(cfg, tp)
           if (ctx.has("decode2d") and ctx.tp_axis) else None)

    def one(kind):
        if kind in ("attn", "local"):
            window = cfg.window if kind == "local" else None
            S = min(window, s_max) if window else s_max
            if d2d:
                g_h, g_s = d2d
                z = jnp.zeros((B_loc, S // g_s, cfg.n_kv // g_h,
                               cfg.head_dim), ctx.compute_dtype)
                return {"k": z, "v": z}
            S_loc = S // tp
            z = jnp.zeros((B_loc, S_loc, cfg.n_kv, cfg.head_dim),
                          ctx.compute_dtype)
            return {"k": z, "v": z}
        if kind == "mlstm":
            return mlstm_state_init(cfg, B_loc, ctx, ctx.compute_dtype)
        if kind == "slstm":
            return slstm_state_init(cfg, B_loc, ctx.compute_dtype)
        if kind == "rglru":
            return rglru_state_init(cfg, B_loc, ctx, ctx.compute_dtype)
        raise ValueError(kind)

    units = {f"b{i}": one(k) for i, k in enumerate(cfg.pattern)}
    units = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_units,) + a.shape), units)
    out = {"units": units}
    if cfg.remainder_kinds:
        out["rem"] = {f"r{i}": one(k)
                      for i, k in enumerate(cfg.remainder_kinds)}
    return out


def _prefill(cfg, ctx, defs, params, batch, s_max, *, unroll: int = 1):
    """Run the prompt, return (cache, last-token logits).

    Attention blocks emit T-sharded KV chunks (re-laid-out to the decode
    cache); recurrent blocks emit their final state straight from the
    chunkwise-parallel form.
    """
    T = (batch["frames"].shape[1] if cfg.frontend == "encodec"
         else batch["tokens"].shape[1] - 1)
    x, _, _ = _embed_sp(cfg, ctx, defs, params, batch, T=T)
    x, states = _scan_units(cfg, ctx, defs, params, x, collect_state=True,
                            unroll=unroll)
    x, rem_states = _rem_blocks(cfg, ctx, defs, params, x,
                                collect_state=True)
    x = rms_norm(x, ctx.gather_w(params["final_ln"],
                                 defs["final_ln"].fsdp_dim), cfg.norm_eps)
    # last-token logits (token T-1 lives on the last tp rank's chunk; after
    # the gather below every chip holds it)
    last = ctx.ag_tokens(x)[:, -1:] if ctx.tp_axis else x[:, -1:]
    w_un = _unembed_weight(cfg, ctx, defs, params)
    logits = decode_logits(last, w_un, ctx, softcap=cfg.logit_softcap)

    cache_units = {}
    for i, k in enumerate(cfg.pattern):
        key = f"b{i}"
        cache_units[key] = _state_to_cache(cfg, ctx, states[key], T, s_max,
                                           k, tdim=2)
    cache = {"units": cache_units}
    if cfg.remainder_kinds:
        cache["rem"] = {f"r{i}": _state_to_cache(cfg, ctx, rem_states[f"r{i}"],
                                                 T, s_max, k)
                        for i, k in enumerate(cfg.remainder_kinds)}
    return cache, logits


def _decode(cfg, ctx, defs, params, cache, token, pos, *, unroll: int = 1):
    """One decode step.  token: (B, 1) int32 (or (B, 1, d_f) frames);
    pos: current position — a scalar shared by the batch, or a (B,) vector
    of per-slot positions (continuous batching over heterogeneous sequence
    lengths).  Returns (new_cache, logits (B, 1, V))."""
    if cfg.frontend == "encodec":
        w_fe = ctx.gather_w(params["frontend"], defs["frontend"].fsdp_dim)
        x = token.astype(ctx.compute_dtype) @ w_fe
    else:
        emb = ctx.gather_w(params["embed"], defs["embed"].fsdp_dim)
        x = embed(token, emb, ctx)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.pos == "sinusoidal":
        if jnp.ndim(pos) == 1:           # per-slot positions: (B, 1, d)
            x = x + sinusoidal_pe(pos, cfg.d_model)[:, None].astype(x.dtype)
        else:
            x = x + sinusoidal_pe(jnp.full((1,), pos),
                                  cfg.d_model)[None].astype(x.dtype)

    kinds = cfg.pattern

    def unit(x, scan_in):
        pu, cu = scan_in
        new_c = {}
        for i, k in enumerate(kinds):
            key = f"b{i}"
            x, st = _block_decode(k, x, pu[key], defs["units"][key], cu[key],
                                  ctx, cfg, pos=pos)
            new_c[key] = st
        return x, new_c

    x, new_units = lax.scan(unit, x, (params["units"], cache["units"]),
                            unroll=unroll)
    new_cache = {"units": new_units}
    if cfg.remainder_kinds:
        x, new_rem = _rem_blocks(cfg, ctx, defs, params, x, decode=True,
                                 pos=pos, cache=cache["rem"])
        new_cache["rem"] = new_rem
    x = rms_norm(x, ctx.gather_w(params["final_ln"],
                                 defs["final_ln"].fsdp_dim), cfg.norm_eps)
    w_un = _unembed_weight(cfg, ctx, defs, params)
    logits = decode_logits(x, w_un, ctx, softcap=cfg.logit_softcap)
    return new_cache, logits


# ---------------------------------------------------------------------------
# Analytic cost notes (loop-body undercount corrections; DESIGN.md §7)
# ---------------------------------------------------------------------------

def _cost_notes(cfg: ModelConfig, *, kind: str, B: int, T: int
                ) -> dict[str, float]:
    """FLOPs hidden from HLO cost analysis by inner sequential loops:
      * flash-attention KV-block scan: all but one block per attention call,
      * sLSTM time scan: all but one timestep,
      * streamed-xent chunk scan: all but one chunk.
    ``mult``: fwd-only (serve) vs fwd+bwd (train, ~3x matmul flops).
    """
    mult = 3.0 if kind == "train" else 1.0
    flops = 0.0
    bytes_ = 0.0
    if kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    for k in cfg.block_kinds:
        if k in ("attn", "local"):
            window = cfg.window if k == "local" else None
            full = attn_flops(B, T, T, cfg.n_heads, cfg.head_dim,
                              causal=True, window=window)
            n_blocks = max(T // KV_BLOCK, 1)
            flops += mult * full * (1.0 - 1.0 / n_blocks)
            kv_bytes = 2 * B * T * cfg.n_kv * cfg.head_dim * 2
            bytes_ += mult * kv_bytes * (n_blocks - 1)
        elif k == "slstm":
            per_layer = slstm_scan_flops(cfg, B, T)
            flops += mult * per_layer * (1.0 - 1.0 / T)
            bytes_ += mult * 8 * B * cfg.d_model * T  # state traffic
    v = cfg.vocab_padded
    n_chunks = max(T // XENT_CHUNK, 1)
    xent = 2.0 * B * T * cfg.d_model * v
    flops += mult * xent * (1.0 - 1.0 / n_chunks)
    bytes_ += mult * (2.0 * cfg.d_model * v) * (n_chunks - 1)
    return {"flops": flops, "bytes": bytes_}
