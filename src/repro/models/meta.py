"""Parameter metadata: global shapes, TP dims, FSDP dims, init rules.

Every param leaf carries a ``PMeta``.  The same tree drives:
  * host-side init (smoke tests, examples),
  * ShapeDtypeStruct construction (dry-run),
  * PartitionSpec construction per (mode, mesh),
  * the gather-at-use calls inside the model (``fsdp_dim``).

Sharding policy (DESIGN.md §5):
  * ``tp_dim``  — sharded over the "model" axis (TP/EP); identical in naive
    and hier modes (the paper keeps computational parallelism unchanged).
  * ``fsdp_dim`` — hier mode only: the dim sharded over "data" — the pod's
    MPI-3 shared window; gathered at use by ``ParallelCtx.gather_w``.
  * ``data_dim`` — serve-only sharded *storage* (expert dff slices): never
    gathered; the compute is written against the local slice.
  * ``ep_dim`` — train only: the expert dim, sharded over ``ep_axes`` (the
    data-parallel axes ``ParallelCtx.expert_axes`` picks); each chip holds
    its own experts, never gathered, and no FSDP dim is taken besides.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


@dataclasses.dataclass
class PMeta:
    shape: tuple[int, ...]
    tp_dim: Optional[int] = None
    fsdp_dim: Optional[int] = None
    data_dim: Optional[int] = None
    ep_dim: Optional[int] = None
    ep_axes: tuple[str, ...] = ()
    init: str = "normal"           # normal | out | zeros | ones | lam
    dtype: jnp.dtype = jnp.float32


def _resolve_fsdp(meta: PMeta, data: int, mode: str, serve: bool,
                  force: bool = False) -> PMeta:
    """Pick the FSDP dim: largest dim divisible by the data-axis size,
    excluding tp/data dims.  Serve: only when explicitly requested upstream
    (meta.fsdp_dim == -2 sentinel, or ``force`` — the ``serve_fsdp`` opt
    keeping serve weights in the pod-shared one-copy-per-node store)."""
    if mode != "hier" or data <= 1 or meta.ep_axes:
        meta.fsdp_dim = None
        return meta
    if serve and not force and meta.fsdp_dim != -2:
        meta.fsdp_dim = None
        return meta
    best, best_size = None, 0
    for dim, s in enumerate(meta.shape):
        if dim == meta.tp_dim or dim == meta.data_dim:
            continue
        if s % data == 0 and s // data >= 1 and s > best_size:
            best, best_size = dim, s
    meta.fsdp_dim = best
    return meta


def attn_mode_for(cfg: ModelConfig, tp: int) -> str:
    return "head_tp" if cfg.n_heads % tp == 0 else "cp"


def decode2d_groups(cfg: ModelConfig, tp: int):
    """(g_h, g_s) factorization of the tp axis for 2D decode attention:
    g_h head groups (must divide H and kv) x g_s seq groups.  None if the
    arch can't use it (g_h would be 1)."""
    g_h = math.gcd(math.gcd(cfg.n_heads, cfg.n_kv), tp)
    if g_h <= 1 or tp % g_h:
        return None
    return g_h, tp // g_h


# ---------------------------------------------------------------------------
# Per-block param/meta definitions
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig, tp: int, serve: bool,
              opts=frozenset()) -> dict[str, PMeta]:
    d, H, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    mode = attn_mode_for(cfg, tp)
    d2d = decode2d_groups(cfg, tp) if ("decode2d" in opts and serve) else None
    if serve and d2d:
        # 2D decode: head-group-sharded weights, duplicated over the seq
        # subgroups (storage x g_s for attn; no per-step gather at all).
        g_h, g_s = d2d
        out = {
            "ln": PMeta((d,), init="zeros"),
            "wq": PMeta((tp, d, H * hd // g_h), tp_dim=0),
            "wkv": PMeta((tp, d, 2, kv * hd // g_h), tp_dim=0),
            "wo": PMeta((tp, H * hd // g_h, d), tp_dim=0, init="out"),
        }
        if cfg.qk_norm:
            out["q_norm"] = PMeta((hd,), init="zeros")
            out["k_norm"] = PMeta((hd,), init="zeros")
        return out
    if serve:
        q_tp = kv_tp = None
        o_tp = None
    else:
        q_tp = 1 if mode == "head_tp" else None
        kv_tp = 2 if (mode == "head_tp" and kv % tp == 0) else None
        o_tp = 0 if mode == "head_tp" else None
    out = {
        "ln": PMeta((d,), init="zeros"),
        "wq": PMeta((d, H * hd), tp_dim=q_tp),
        "wkv": PMeta((d, 2, kv * hd), tp_dim=kv_tp),
        "wo": PMeta((H * hd, d), tp_dim=o_tp, init="out"),
    }
    if cfg.qk_norm:
        out["q_norm"] = PMeta((hd,), init="zeros")
        out["k_norm"] = PMeta((hd,), init="zeros")
    if serve and _attn_bytes(cfg) > 4e9:
        # big-attn serve (qwen3-moe): keep the paper's one-copy-per-pod store
        for k in ("wq", "wkv", "wo"):
            out[k].fsdp_dim = -2  # sentinel: resolve even in serve mode
    return out


def _attn_bytes(cfg: ModelConfig) -> float:
    d, H, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    per = d * (H + 2 * kv) * hd + H * hd * d
    n_attn = sum(1 for k in cfg.block_kinds if k in ("attn", "local"))
    return 2.0 * per * n_attn


def ffn_defs(cfg: ModelConfig, tp: int) -> dict[str, PMeta]:
    d, dff = cfg.d_model, cfg.d_ff
    g = 1 if cfg.act == "gelu" else 2
    return {
        "ln": PMeta((d,), init="zeros"),
        "w_in": PMeta((d, g, dff), tp_dim=2),
        "w_out": PMeta((dff, d), tp_dim=0, init="out"),
    }


def moe_defs(cfg: ModelConfig, tp: int, serve: bool,
             ep_axes: tuple[str, ...] = ()) -> dict[str, PMeta]:
    """Expert weights are stored ``(tp, E / ep_tp, ...)``: the model axis
    factored as ``MoESpec.ep_tp``.  Train: each tp group's experts are
    spread further over ``ep_axes`` (dim 1).  Serve: the dff slice is
    sharded over data instead."""
    d = cfg.d_model
    spec = cfg.moe
    ep, tp_ff = spec.ep_tp(tp)
    e_loc = spec.num_experts // ep
    n_ff = spec.d_ff_expert // tp_ff
    held = {} if serve or not ep_axes else {"ep_dim": 1, "ep_axes": ep_axes}
    return {
        "ln": PMeta((d,), init="zeros"),
        "router": PMeta((d, spec.num_experts)),
        "w_in": PMeta((tp, e_loc, d, 2, n_ff), tp_dim=0,
                      data_dim=4 if serve else None, **held),
        "w_out": PMeta((tp, e_loc, n_ff, d), tp_dim=0, init="out",
                       data_dim=2 if serve else None, **held),
    }


def mlstm_defs(cfg: ModelConfig, tp: int) -> dict[str, PMeta]:
    d, din, nh = cfg.d_model, cfg.d_inner, cfg.n_heads
    hd = din // nh
    return {
        "ln": PMeta((d,), init="zeros"),
        "w_up": PMeta((d, 2, din), tp_dim=2),
        "conv": PMeta((din, cfg.conv_kernel), tp_dim=0),
        "wq": PMeta((nh, hd, hd)),
        "wk": PMeta((nh, hd, hd)),
        "wv": PMeta((nh, hd, hd)),
        "wif": PMeta((nh, hd, 2)),
        "w_down": PMeta((din, d), tp_dim=0, init="out"),
    }


def slstm_defs(cfg: ModelConfig, tp: int) -> dict[str, PMeta]:
    d, nh = cfg.d_model, cfg.n_heads
    dh = d // nh
    return {
        "ln": PMeta((d,), init="zeros"),
        "w_x": PMeta((d, 4, d)),
        "r": PMeta((nh, dh, 4, dh)),
        "b": PMeta((4, d), init="zeros"),
        "w_out": PMeta((d, d), init="out"),
    }


def rglru_defs(cfg: ModelConfig, tp: int) -> dict[str, PMeta]:
    d, dr = cfg.d_model, cfg.rnn_width
    return {
        "ln": PMeta((d,), init="zeros"),
        "w_x": PMeta((d, 2, dr), tp_dim=2),
        "conv": PMeta((dr, cfg.conv_kernel), tp_dim=0),
        "w_rg": PMeta((d, 2, dr), tp_dim=2),
        "lam": PMeta((dr,), tp_dim=0, init="lam"),
        "w_out": PMeta((dr, d), tp_dim=0, init="out"),
    }


def block_defs(kind: str, cfg: ModelConfig, tp: int, serve: bool,
               opts=frozenset(), ep_axes: tuple[str, ...] = ()) -> dict:
    if kind in ("attn", "local"):
        out = {"attn": attn_defs(cfg, tp, serve, opts)}
        if cfg.moe:
            out["moe"] = moe_defs(cfg, tp, serve, ep_axes)
        elif cfg.d_ff:
            out["ffn"] = ffn_defs(cfg, tp)
        return out
    if kind == "mlstm":
        return {"mlstm": mlstm_defs(cfg, tp)}
    if kind == "slstm":
        return {"slstm": slstm_defs(cfg, tp)}
    if kind == "rglru":
        out = {"rglru": rglru_defs(cfg, tp)}
        if cfg.moe:
            out["moe"] = moe_defs(cfg, tp, serve, ep_axes)
        elif cfg.d_ff:
            out["ffn"] = ffn_defs(cfg, tp)
        return out
    raise ValueError(kind)


def model_defs(cfg: ModelConfig, tp: int, data: int, mode: str,
               serve: bool = False, opts=frozenset(),
               ep_axes: tuple[str, ...] = ()) -> dict:
    """Full meta tree.  'units' metas describe PER-LAYER shapes (they get a
    stacked leading dim at materialization).  ``ep_axes``: the axes the
    train path spreads each layer's experts over."""
    d = cfg.d_model
    defs: dict = {
        "embed": PMeta((cfg.vocab_padded, d), tp_dim=0),
        "final_ln": PMeta((d,), init="zeros"),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = PMeta((d, cfg.vocab_padded), tp_dim=1)
    if cfg.frontend:
        defs["frontend"] = PMeta((cfg.d_frontend, d))
    defs["units"] = {f"b{i}": block_defs(k, cfg, tp, serve, opts, ep_axes)
                     for i, k in enumerate(cfg.pattern)}
    if cfg.remainder_kinds:
        defs["rem"] = {f"r{i}": block_defs(k, cfg, tp, serve, opts, ep_axes)
                       for i, k in enumerate(cfg.remainder_kinds)}
    force = serve and "serve_fsdp" in opts
    return jax.tree.map(
        lambda m: _resolve_fsdp(m, data, mode, serve, force), defs,
        is_leaf=lambda x: isinstance(x, PMeta))


# ---------------------------------------------------------------------------
# Materialization: init / abstract shapes / PartitionSpecs
# ---------------------------------------------------------------------------

def _stacked_shape(meta: PMeta, stacked: Optional[int]) -> tuple[int, ...]:
    return ((stacked,) + meta.shape) if stacked else meta.shape


def init_leaf(meta: PMeta, key, n_layers: int, stacked: Optional[int]
              ) -> jax.Array:
    shape = _stacked_shape(meta, stacked)
    if meta.init == "zeros":
        return jnp.zeros(shape, meta.dtype)
    if meta.init == "ones":
        return jnp.ones(shape, meta.dtype)
    if meta.init == "lam":
        # RG-LRU: target a in [0.9, 0.999] at r=1 -> softplus(lam) = -log(a)/C
        a = np.linspace(0.9, 0.999, meta.shape[-1])
        lam = np.log(np.expm1(np.maximum(-np.log(a) / 8.0, 1e-8)))
        out = np.broadcast_to(lam, shape).astype(np.float32)
        return jnp.asarray(out)
    scale = 0.02
    if meta.init == "out":
        scale = 0.02 / math.sqrt(2.0 * max(n_layers, 1))
    return (jax.random.normal(key, shape, meta.dtype) * scale)


def init_params(defs: dict, cfg: ModelConfig, seed: int = 0) -> dict:
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, PMeta))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    paths = jax.tree_util.tree_leaves_with_path(
        defs, is_leaf=lambda x: isinstance(x, PMeta))

    def depth_of(path) -> Optional[int]:
        return cfg.n_units if (path and getattr(path[0], "key", None)
                               == "units") else None

    out = [init_leaf(m, k, cfg.n_layers, depth_of(p))
           for (p, m), k in zip(paths, keys)]
    return jax.tree.unflatten(treedef, out)


def abstract_params(defs: dict, cfg: ModelConfig, specs: dict) -> dict:
    """ShapeDtypeStructs with shardings attached (dry-run input)."""
    def mk(path, meta, spec):
        stacked = cfg.n_units if (path and getattr(path[0], "key", None)
                                  == "units") else None
        return jax.ShapeDtypeStruct(_stacked_shape(meta, stacked), meta.dtype,
                                    sharding=spec)
    paths = jax.tree_util.tree_leaves_with_path(
        defs, is_leaf=lambda x: isinstance(x, PMeta))
    spec_leaves = jax.tree.leaves(specs,
                                  is_leaf=lambda x: isinstance(x, P))
    treedef = jax.tree.structure(defs,
                                 is_leaf=lambda x: isinstance(x, PMeta))
    return jax.tree.unflatten(
        treedef, [mk(p, m, s) for (p, m), s in zip(paths, spec_leaves)])


def param_specs(defs: dict, cfg: ModelConfig, *, tp_axis: Optional[str],
                fsdp_axis: Optional[str]) -> dict:
    """PartitionSpec tree (stacked dims accounted for)."""
    def mk(path, meta: PMeta):
        stacked = bool(path and getattr(path[0], "key", None) == "units")
        off = 1 if stacked else 0
        ndim = len(meta.shape) + off
        spec = [None] * ndim
        if meta.tp_dim is not None and tp_axis:
            spec[meta.tp_dim + off] = tp_axis
        if meta.fsdp_dim is not None and fsdp_axis:
            spec[meta.fsdp_dim + off] = fsdp_axis
        if meta.data_dim is not None and fsdp_axis:
            spec[meta.data_dim + off] = fsdp_axis
        if meta.ep_dim is not None:
            spec[meta.ep_dim + off] = meta.ep_axes
        return P(*spec)

    paths = jax.tree_util.tree_leaves_with_path(
        defs, is_leaf=lambda x: isinstance(x, PMeta))
    treedef = jax.tree.structure(defs,
                                 is_leaf=lambda x: isinstance(x, PMeta))
    return jax.tree.unflatten(treedef, [mk(p, m) for p, m in paths])


def relayout_attn_decode2d(w, cfg: ModelConfig, tp: int, kind: str):
    """Re-layout a baseline attention weight into the decode2d storage:
    entry[r] = the head-group slice for chip r (duplicated over the g_s seq
    chips of each head group).  kind: wq (d, H*hd) | wkv (d, 2, kv*hd) |
    wo (H*hd, d)."""
    import numpy as np
    g = decode2d_groups(cfg, tp)
    assert g, "arch has no decode2d factorization"
    g_h, g_s = g
    hd = cfg.head_dim
    out = []
    for r in range(tp):
        hg = r // g_s
        if kind == "wq":
            ncol = cfg.n_heads * hd // g_h
            out.append(w[:, hg * ncol:(hg + 1) * ncol])
        elif kind == "wkv":
            ncol = cfg.n_kv * hd // g_h
            out.append(w[:, :, hg * ncol:(hg + 1) * ncol])
        elif kind == "wo":
            nrow = cfg.n_heads * hd // g_h
            out.append(w[hg * nrow:(hg + 1) * nrow, :])
        else:
            raise ValueError(kind)
    return np.stack([np.asarray(x) for x in out])
