"""xLSTM blocks: mLSTM (chunked-parallel linear attention with matrix memory)
and sLSTM (sequential scalar-memory RNN).  arXiv:2405.04517.

TPU adaptation (DESIGN.md §5):
  * mLSTM is evaluated in *chunkwise-parallel* form — intra-chunk masked
    linear attention + cross-chunk state recurrence via
    ``lax.associative_scan`` — so the lowering contains NO sequential loops
    and HLO cost analysis counts every FLOP.
  * Sharding: heads x v-slices over tp (head-major flattened inner dim); q/k
    are computed per head group from a group all-gather.
  * sLSTM is inherently sequential (recurrent nonlinearity): it runs as a
    ``lax.scan`` over time, batch-sharded over tp groups; its recurrent FLOPs
    are reported analytically (``slstm_scan_flops``) to the roofline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm.primitives import scoped
from repro.models.layers import rms_norm
from repro.models.parallel import ParallelCtx


def causal_conv1d(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv: x (B, T, C), w (C, K)."""
    K = w.shape[1]
    out = x * w[:, -1]
    for j in range(1, K):
        shifted = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :-j]
        out = out + shifted * w[:, K - 1 - j]
    return out


def _head_layout(ctx: ParallelCtx, nh: int, hd: int):
    """hpc: heads per chip, g: chips per head, vs: local v-slice width."""
    tp = ctx.tp
    hpc = max(nh // tp, 1)
    g = max(tp // nh, 1)
    return hpc, g, hd // g


# ---------------------------------------------------------------------------
# mLSTM chunkwise-parallel scan
# ---------------------------------------------------------------------------

def mlstm_parallel(q, k, v, ig, fg, *, chunk: int = 128,
                   return_state: bool = False):
    """q, k: (B, T, h, hd); v: (B, T, h, vs); ig, fg: (B, T, h) raw gates.
    Returns (B, T, h, vs) (+ final stabilized state for decode continuation
    when ``return_state``).  Stabilized with a per-sequence input-gate max."""
    B, T, h, hd = q.shape
    vs = v.shape[-1]
    S = min(chunk, T)
    assert T % S == 0, f"T={T} not divisible by chunk={S}"
    nc = T // S

    log_f = jax.nn.log_sigmoid(fg.astype(jnp.float32))       # (B, T, h)
    m = lax.stop_gradient(jnp.max(ig, axis=1, keepdims=True))  # (B, 1, h)
    li = (ig - m).astype(jnp.float32)                        # log i', <= 0

    def cshape(x):  # (B, T, ...) -> (B, nc, S, ...)
        return x.reshape((B, nc, S) + x.shape[2:])

    qc, kc, vc = cshape(q.astype(jnp.float32)), cshape(k.astype(jnp.float32)), \
        cshape(v.astype(jnp.float32))
    lfc, lic = cshape(log_f), cshape(li)
    F = jnp.cumsum(lfc, axis=2)                              # incl. cumsum
    Ftot = F[:, :, -1]                                       # (B, nc, h)

    # intra-chunk: A[t, j] = exp(F[t]-F[j]+li[j]) * (q_t . k_j), j <= t
    smat = jnp.einsum("bcthd,bcshd->bchts", qc, kc) / (hd ** 0.5)
    logw = (F[:, :, :, None, :] - F[:, :, None, :, :]
            + lic[:, :, None, :, :])                         # (B,c,t,s,h)
    tri = jnp.tril(jnp.ones((S, S), bool))
    w = jnp.where(tri[None, None, :, :, None], jnp.exp(logw), 0.0)
    wq = w.transpose(0, 1, 4, 2, 3) * smat                   # (B,c,h,t,s)
    o_intra = jnp.einsum("bchts,bcshv->bcthv", wq, vc)
    den_intra = jnp.sum(wq, axis=-1).transpose(0, 1, 3, 2)   # (B,c,t,h)

    # chunk summaries: dC = sum_j exp(Ftot - F[j] + li[j]) k_j v_j^T
    wsum = jnp.exp(Ftot[:, :, None, :] - F + lic)            # (B,c,S,h)
    dC = jnp.einsum("bcsh,bcshd,bcshv->bchdv", wsum, kc, vc)
    dn = jnp.einsum("bcsh,bcshd->bchd", wsum, kc)
    D = jnp.exp(Ftot)                                        # (B,c,h)

    # cross-chunk associative prefix:  (D, dC, dn) o (D', dC', dn')
    def combine(a, b):
        Da, Ca, na = a
        Db, Cb, nb = b
        return (Da * Db, Db[..., None, None] * Ca + Cb,
                Db[..., None] * na + nb)

    Dp, Cp, np_ = lax.associative_scan(combine, (D, dC, dn), axis=1)
    zC = jnp.zeros_like(Cp[:, :1])
    zn = jnp.zeros_like(np_[:, :1])
    C_prev = jnp.concatenate([zC, Cp[:, :-1]], axis=1)       # state before c
    n_prev = jnp.concatenate([zn, np_[:, :-1]], axis=1)

    decay_t = jnp.exp(F)                                     # (B,c,S,h)
    o_inter = jnp.einsum("bcthd,bchdv->bcthv", qc, C_prev) \
        * decay_t[..., None] / (hd ** 0.5)
    den_inter = jnp.einsum("bcthd,bchd->bcth", qc, n_prev) \
        * decay_t / (hd ** 0.5)

    num = o_intra + o_inter
    den = den_intra + den_inter                              # (B,c,t,h)
    den = jnp.maximum(jnp.abs(den), 1.0)
    out = num / den[..., None]
    out = out.reshape(B, T, h, vs).astype(q.dtype)
    if return_state:
        state = {"C": Cp[:, -1], "n": np_[:, -1],
                 "m": jnp.squeeze(m, 1).astype(jnp.float32)}
        return out, state
    return out


def mlstm_decode_step(state: dict, q, k, v, ig, fg):
    """One-token recurrence.  state: C (B,h,hd,vs), n (B,h,hd), m (B,h);
    q,k: (B,h,hd); v: (B,h,vs)."""
    C, n, m = state["C"], state["n"], state["m"]
    hd = q.shape[-1]
    log_f = jax.nn.log_sigmoid(fg.astype(jnp.float32))
    m_new = jnp.maximum(log_f + m, ig.astype(jnp.float32))
    fp = jnp.exp(log_f + m - m_new)
    ip = jnp.exp(ig - m_new)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    C = fp[..., None, None] * C + ip[..., None, None] \
        * (kf[..., :, None] * vf[..., None, :])
    n = fp[..., None] * n + ip[..., None] * kf
    qf = q.astype(jnp.float32) / (hd ** 0.5)
    num = jnp.einsum("bhd,bhdv->bhv", qf, C)
    den = jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n))
    den = jnp.maximum(den, jnp.exp(-m_new))
    out = num / den[..., None]
    return {"C": C, "n": n, "m": m_new}, out.astype(q.dtype)


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------

def _mlstm_qkv_gates(xh, p, ctx: ParallelCtx, nh: int, hd: int, hpc: int):
    """xh: (B, T, hpc, hd) gathered head inputs -> q, k (B,T,hpc,hd),
    gates (B,T,hpc,2).  Weight tensors are (nh, hd, .) stored tp-replicated;
    slice this chip's heads."""
    h0 = (ctx.tp_rank * hpc) % nh if ctx.tp_axis else 0
    wq = lax.dynamic_slice_in_dim(p["wq"], h0, hpc, 0)
    wk = lax.dynamic_slice_in_dim(p["wk"], h0, hpc, 0)
    wif = lax.dynamic_slice_in_dim(p["wif"], h0, hpc, 0)
    q = jnp.einsum("bthd,hde->bthe", xh, wq.astype(xh.dtype))
    k = jnp.einsum("bthd,hde->bthe", xh, wk.astype(xh.dtype))
    gates = jnp.einsum("bthd,hdg->bthg", xh, wif.astype(xh.dtype))
    return q, k, gates


def mlstm_block(x_sp, p, meta, ctx: ParallelCtx, cfg, *, chunk: int = 128,
                state: dict | None = None, decode: bool = False,
                return_state: bool = False):
    """x_sp: (B, T/tp, d) (train) or (B, 1, d) (decode)."""
    nh, din = cfg.n_heads, cfg.d_inner
    hd = din // nh
    hpc, g, vs = _head_layout(ctx, nh, hd)
    eps = cfg.norm_eps

    h = rms_norm(x_sp, ctx.gather_w(p["ln"], meta["ln"].fsdp_dim), eps)
    hg = h if decode else ctx.ag_tokens(h)                   # (B, T, d)
    B, T, _ = hg.shape

    w_up = ctx.gather_w(p["w_up"], meta["w_up"].fsdp_dim)    # (d, 2, din/tp)
    u = jnp.einsum("btd,dgf->btgf", hg, w_up)
    z_loc, x_loc = u[:, :, 0], u[:, :, 1]                    # (B,T,din/tp)

    conv_w = ctx.gather_w(p["conv"], meta["conv"].fsdp_dim)  # (din/tp, K)
    if decode:
        cx = state["conv"]                                   # (B, K-1, C)
        xin = jnp.concatenate([cx, x_loc], axis=1)
        xc = causal_conv1d(xin, conv_w)[:, -1:]
        new_conv = xin[:, 1:]
    else:
        xc = causal_conv1d(x_loc, conv_w)
    xc = jax.nn.silu(xc)

    # per-head-group gather: (B,T,hpc,vs) -> (B,T,hpc,hd)
    xh = ctx.group_all_gather(xc.reshape(B, T, hpc, vs), group=g, dim=3)
    q, k, gates = _mlstm_qkv_gates(xh, {k_: ctx.gather_w(p[k_],
                                                         meta[k_].fsdp_dim)
                                        for k_ in ("wq", "wk", "wif")},
                                   ctx, nh, hd, hpc)
    # v: full-head input x local v-slice of Wv
    wv = ctx.gather_w(p["wv"], meta["wv"].fsdp_dim)          # (nh, hd, hd)
    h0 = (ctx.tp_rank * hpc) % nh if ctx.tp_axis else 0
    sl = (ctx.tp_rank % g) * vs if ctx.tp_axis else 0
    wv = lax.dynamic_slice(wv, (h0, 0, sl), (hpc, hd, vs))
    v = jnp.einsum("bthd,hdv->bthv", xh, wv.astype(xh.dtype))

    ig, fg = gates[..., 0], gates[..., 1]
    if decode:
        new_state, o = mlstm_decode_step(
            {k2: state[k2] for k2 in ("C", "n", "m")},
            q[:, 0], k[:, 0], v[:, 0], ig[:, 0], fg[:, 0])
        o = o[:, None]
        new_state["conv"] = new_conv
    elif return_state:
        o, new_state = mlstm_parallel(q, k, v, ig, fg, chunk=min(chunk, T),
                                      return_state=True)
        K = cfg.conv_kernel
        new_state["conv"] = x_loc[:, -(K - 1):].astype(x_loc.dtype)
    else:
        o = mlstm_parallel(q, k, v, ig, fg, chunk=min(chunk, T))
        new_state = None

    o = o.reshape(B, T, hpc * vs) * jax.nn.silu(z_loc)
    w_down = ctx.gather_w(p["w_down"], meta["w_down"].fsdp_dim)  # (din/tp, d)
    y = o @ w_down
    if decode:
        out = x_sp + ctx.psum_tp(y)
        return out, new_state
    out = x_sp + ctx.rs_tokens(y)
    return (out, new_state) if return_state else out


def mlstm_state_init(cfg, B: int, ctx: ParallelCtx, dtype=jnp.float32):
    nh = cfg.n_heads
    hd = cfg.d_inner // nh
    hpc, g, vs = _head_layout(ctx, nh, hd)
    return {"C": jnp.zeros((B, hpc, hd, vs), jnp.float32),
            "n": jnp.zeros((B, hpc, hd), jnp.float32),
            "m": jnp.full((B, hpc), -1e30, jnp.float32),
            "conv": jnp.zeros((B, cfg.conv_kernel - 1,
                               cfg.d_inner // max(ctx.tp, 1)), dtype)}


# ---------------------------------------------------------------------------
# sLSTM (sequential; batch-sharded over tp)
# ---------------------------------------------------------------------------

def slstm_cell(carry, gx, r_w, nh: int):
    """carry: (h, c, n, m) each (b, d); gx: (b, 4, d) input-side gates;
    r_w: (nh, dh, 4, dh) recurrent block-diagonal weights."""
    h, c, n, m = carry
    b, d = h.shape
    dh = d // nh
    hr = h.reshape(b, nh, dh)
    gr = jnp.einsum("bhd,hdgf->bhgf", hr, r_w)               # (b, nh, 4, dh)
    g = gx + gr.transpose(0, 2, 1, 3).reshape(b, 4, d)
    it, ft, zt, ot = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
    m_new = jnp.maximum(ft + m, it)
    ip = jnp.exp(it - m_new)
    fp = jnp.exp(ft + m - m_new)
    c_new = fp * c + ip * jnp.tanh(zt)
    n_new = fp * n + ip
    h_new = jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, 1e-6)
    return (h_new, c_new, n_new, m_new)


def slstm_block(x_sp, p, meta, ctx: ParallelCtx, cfg, *,
                state: dict | None = None, decode: bool = False,
                return_state: bool = False):
    d, nh = cfg.d_model, cfg.n_heads
    eps = cfg.norm_eps
    h_in = rms_norm(x_sp, ctx.gather_w(p["ln"], meta["ln"].fsdp_dim), eps)
    hg = h_in if decode else ctx.ag_tokens(h_in)             # (B, T, d)
    B, T, _ = hg.shape

    w_x = ctx.gather_w(p["w_x"], meta["w_x"].fsdp_dim)       # (d, 4, d)
    r_w = ctx.gather_w(p["r"], meta["r"].fsdp_dim).astype(jnp.float32)
    b_g = ctx.gather_w(p["b"], meta["b"].fsdp_dim)           # (4, d)
    gx = jnp.einsum("btd,dgf->btgf", hg, w_x) + b_g          # (B, T, 4, d)
    gx = gx.astype(jnp.float32)

    if decode:
        carry = (state["h"], state["c"], state["n"], state["m"])
        new = slstm_cell(carry, gx[:, 0], r_w, nh)
        hs = new[0][:, None].astype(hg.dtype)                # (B, 1, d)
        new_state = dict(zip(("h", "c", "n", "m"), new))
        w_out = ctx.gather_w(p["w_out"], meta["w_out"].fsdp_dim)
        return x_sp + hs @ w_out, new_state

    # batch-shard the sequential scan over tp groups
    tp = ctx.tp
    nb = min(tp, B)            # distinct sequences handled in parallel
    cps = tp // nb             # chips replicating each sequence
    bs = B // nb
    if ctx.tp_axis:
        seq_idx = ctx.tp_rank // cps
        primary = (ctx.tp_rank % cps) == 0
        gxm = lax.dynamic_slice_in_dim(gx, seq_idx * bs, bs, 0)
    else:
        seq_idx, primary, gxm = 0, True, gx

    z = jnp.zeros((bs, d), jnp.float32)
    carry0 = (z, z, z, jnp.full((bs, d), -1e30, jnp.float32))

    def step(carry, gxt):
        new = slstm_cell(carry, gxt, r_w, nh)
        return new, new[0]

    final, hs = lax.scan(step, carry0, gxm.swapaxes(0, 1))   # (T, bs, d)
    hs = hs.swapaxes(0, 1).astype(hg.dtype)                  # (bs, T, d)

    new_state = None
    if return_state:
        def widen(s):  # (bs, d) -> (B, d) replicated via masked psum
            if not ctx.tp_axis:
                return s
            full = jnp.zeros((B, d), s.dtype)
            full = lax.dynamic_update_slice_in_dim(
                full, s * jnp.asarray(primary, s.dtype), seq_idx * bs, 0)
            # raw-collective: flat tp fast path (one group, one schedule)
            return scoped(lax.psum, full, ctx.tp_axis)
        new_state = dict(zip(("h", "c", "n", "m"), map(widen, final)))

    w_out = ctx.gather_w(p["w_out"], meta["w_out"].fsdp_dim)  # (d, d)
    y_me = hs @ w_out
    if ctx.tp_axis:
        y_full = jnp.zeros((B, T, d), y_me.dtype)
        y_full = lax.dynamic_update_slice_in_dim(
            y_full, y_me * jnp.float32(primary).astype(y_me.dtype),
            seq_idx * bs, 0)
        out = x_sp + ctx.rs_tokens(y_full)
    else:
        out = x_sp + y_me
    return (out, new_state) if return_state else out


def slstm_state_init(cfg, B: int, dtype=jnp.float32):
    d = cfg.d_model
    z = jnp.zeros((B, d), jnp.float32)
    return {"h": z, "c": z, "n": z, "m": jnp.full((B, d), -1e30, jnp.float32)}


def slstm_scan_flops(cfg, B: int, T: int) -> float:
    """Analytic recurrent FLOPs hidden inside the time scan (per layer)."""
    d, nh = cfg.d_model, cfg.n_heads
    dh = d // nh
    return 2.0 * B * T * nh * dh * 4 * dh
