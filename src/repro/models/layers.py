"""Shared layer math: norms, positions, embeddings, FFN, streamed loss.

Everything is a pure function of (params, inputs, ctx) running inside a
shard_map body (or single-device when ctx.tp_axis is None).  Residual stream
is sequence-parallel: (B, T/tp, d) between blocks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm.primitives import scoped
from repro.models.parallel import ParallelCtx


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(dt)


def activation(kind: str, gate: jax.Array, up: Optional[jax.Array]
               ) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(gate)
    fn = jax.nn.gelu if kind == "geglu" else jax.nn.silu
    return fn(gate) * up


def rope(x: jax.Array, positions: jax.Array, theta: float,
         compute_dtype=None) -> jax.Array:
    """x: (..., T, n, hd); positions: (T,) global token positions.

    ``compute_dtype``: rotate in this dtype (bf16_rope opt) — the angle
    tables stay fp32, only the (B,T,n,hd)-sized products narrow."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (T, hd/2)
    dt = compute_dtype or jnp.float32
    cos = jnp.cos(ang)[None, :, None, :].astype(dt)
    sin = jnp.sin(ang)[None, :, None, :].astype(dt)
    x1, x2 = jnp.split(x.astype(dt), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def rope_decode(x: jax.Array, pos, theta: float,
                compute_dtype=None) -> jax.Array:
    """Decode-step rope: x is (B, 1, n, hd); ``pos`` is a position scalar
    shared by the batch, or a (B,) vector of per-slot positions (continuous
    batching).  The scalar path matches ``rope(x, pos[None], ...)``."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return rope(x, pos[None], theta, compute_dtype)
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]     # (B, hd/2)
    dt = compute_dtype or jnp.float32
    cos = jnp.cos(ang)[:, None, None, :].astype(dt)
    sin = jnp.sin(ang)[:, None, None, :].astype(dt)
    x1, x2 = jnp.split(x.astype(dt), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def sinusoidal_pe(positions: jax.Array, d: int) -> jax.Array:
    """(T,) -> (T, d) classic transformer PE."""
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding / unembedding
# ---------------------------------------------------------------------------

def embed(ids: jax.Array, emb: jax.Array, ctx: ParallelCtx, *,
          sp: bool = False) -> jax.Array:
    """Vocab-parallel lookup.  emb: local (V/tp, d) vocab shard.

    ``sp=True``: ids are the FULL (B, T) sequence; every rank looks all
    tokens up in its vocab shard and the partials are reduce-SCATTERED over
    the token dim, yielding the (B, T/tp, d) sequence-parallel stream (one
    collective, each rank keeps its own chunk — summing full partials with a
    plain psum would mix different ranks' token chunks).
    ``sp=False`` (decode): ids are replicated; partials are psum'd.
    """
    v_loc = emb.shape[0]
    off = ctx.tp_rank * v_loc
    local = ids - off
    valid = (local >= 0) & (local < v_loc)
    local = jnp.clip(local, 0, v_loc - 1)
    out = (jnp.take(emb, local, axis=0)
           * valid[..., None]).astype(ctx.compute_dtype)
    if sp:
        return ctx.rs_tokens(out)
    return ctx.psum_tp(out)


def unembed_xent(x_sp: jax.Array, labels: jax.Array, mask: jax.Array,
                 unemb: jax.Array, ctx: ParallelCtx, *,
                 chunk: int = 512, softcap: Optional[float] = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Streamed vocab-parallel cross-entropy (Megatron-SP layout).

    x_sp: (B, T/tp, d) SP activations; labels/mask: FULL (B, T);
    unemb: local (d, V/tp).  x is gathered to full T first so the vocab
    psums (max / sum-exp / correct-logit) combine the SAME tokens on every
    tp rank; the resulting nll is tp-replicated, so the sums are divided by
    tp — the caller's flat psum over (tp, dp) is then exact.  Logits are
    never materialized beyond (B, chunk, V/tp).
    NOTE: the chunk scan body is counted once by HLO cost analysis; the
    roofline adds the analytic 2*B*T*d*V correction (see analysis/roofline).
    """
    B, _, d = x_sp.shape
    xg = ctx.ag_tokens(x_sp)                               # (B, T, d)
    T = xg.shape[1]
    v_loc = unemb.shape[1]
    off = ctx.tp_rank * v_loc
    chunk = min(chunk, T)
    n_chunks = T // chunk
    rem = T - n_chunks * chunk

    def chunk_loss(xc, lc, mc):
        # bf16_xent opt: every (B, chunk, V/tp)-sized array stays narrow;
        # reductions accumulate fp32 (sum dtype), stats are per-row scalars.
        ldt = ctx.compute_dtype if ctx.has("bf16_xent") else jnp.float32
        logits = xc.astype(ldt) @ unemb.astype(ldt)
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        # stabilizer only — gradients flow through se (exact softmax grad)
        mx = lax.stop_gradient(ctx.pmax_tp(
            jnp.max(logits, axis=-1).astype(jnp.float32)))
        p = jnp.exp(logits - mx[..., None].astype(ldt))
        se = ctx.psum_tp(jnp.sum(p, axis=-1, dtype=jnp.float32))
        lse = mx + jnp.log(se)
        lloc = lc - off
        ok = (lloc >= 0) & (lloc < v_loc)
        lloc = jnp.clip(lloc, 0, v_loc - 1)
        corr = ctx.psum_tp(
            (jnp.take_along_axis(logits, lloc[..., None], axis=-1)[..., 0]
             * ok).astype(jnp.float32))
        nll = (lse - corr) * mc
        return jnp.sum(nll), jnp.sum(mc)

    total, count = jnp.float32(0.0), jnp.float32(0.0)
    if n_chunks:
        xs = xg[:, :n_chunks * chunk].reshape(B, n_chunks, chunk, d)
        ls = labels[:, :n_chunks * chunk].reshape(B, n_chunks, chunk)
        ms = mask[:, :n_chunks * chunk].reshape(B, n_chunks, chunk)

        def body(carry, inp):
            xc, lc, mc = inp
            s, c = chunk_loss(xc, lc, mc)
            return (carry[0] + s, carry[1] + c), None

        (total, count), _ = lax.scan(
            body, (total, count),
            (xs.swapaxes(0, 1), ls.swapaxes(0, 1), ms.swapaxes(0, 1)))
    if rem:
        s, c = chunk_loss(xg[:, n_chunks * chunk:],
                          labels[:, n_chunks * chunk:],
                          mask[:, n_chunks * chunk:])
        total, count = total + s, count + c
    return total / ctx.tp, count / ctx.tp


def decode_logits(x: jax.Array, unemb: jax.Array, ctx: ParallelCtx, *,
                  softcap: Optional[float] = None) -> jax.Array:
    """x: (B, 1, d) -> full-vocab logits (B, 1, V) (gathered over tp)."""
    logits = x.astype(jnp.float32) @ unemb.astype(jnp.float32)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    if ctx.tp_axis:
        logits = scoped(lax.all_gather,  # raw-collective: flat tp fast path
                        logits, ctx.tp_axis, axis=-1, tiled=True)
    return logits


# ---------------------------------------------------------------------------
# Dense FFN (Megatron-SP: AG tokens -> col/row parallel -> RS tokens)
# ---------------------------------------------------------------------------

@jax.named_scope("mlp")
def ffn(x_sp: jax.Array, p: dict, meta: dict, ctx: ParallelCtx, *,
        act: str, eps: float) -> jax.Array:
    # issue every window read up front (issue-early discipline: the weight
    # gathers are independent of the token math, so XLA is free to overlap
    # them with the norm/SP-gather below — same values, earlier issue)
    w_ln = ctx.gather_w(p["ln"], meta["ln"].fsdp_dim)
    # w_in: (d, g, dff) with g in {1 (gelu), 2 (gated)}; tp shards dff so the
    # gate/up halves stay aligned under contiguous sharding.
    w_in = ctx.gather_w(p["w_in"], meta["w_in"].fsdp_dim)  # (d, g, dff/tp)
    h = rms_norm(x_sp, w_ln, eps)
    hg = ctx.ag_tokens(h)                                  # (B, T, d)
    u = jnp.einsum("btd,dgf->btgf", hg, w_in)
    if act == "gelu":
        a = activation(act, u[:, :, 0], None)
    else:
        a = activation(act, u[:, :, 0], u[:, :, 1])
    # down-projection through the fused gather_w fast path: with the
    # "overlap" opt the FSDP window read streams behind the panel matmuls;
    # without it this is exactly a @ gather_w(w_out)  (w_out: (dff/tp, d))
    y = ctx.ag_matmul(a, p["w_out"], meta["w_out"].fsdp_dim)
    return x_sp + ctx.rs_tokens(y)


def ffn_decode(x: jax.Array, p: dict, meta: dict, ctx: ParallelCtx, *,
               act: str, eps: float) -> jax.Array:
    """Decode-shape FFN: 1 token, no SP AG (token replicated over tp);
    col/row parallel with a single psum."""
    w_ln = ctx.gather_w(p["ln"], meta["ln"].fsdp_dim)
    w_in = ctx.gather_w(p["w_in"], meta["w_in"].fsdp_dim)
    h = rms_norm(x, w_ln, eps)
    u = jnp.einsum("btd,dgf->btgf", h, w_in)
    if act == "gelu":
        a = activation(act, u[:, :, 0], None)
    else:
        a = activation(act, u[:, :, 0], u[:, :, 1])
    w_out = ctx.gather_w(p["w_out"], meta["w_out"].fsdp_dim)
    return x + ctx.psum_tp(a @ w_out)
