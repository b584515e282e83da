"""Plain float32 reference of a dense decoder-only transformer train step.

Written in straightforward ``jax.numpy`` from the configuration file alone;
it imports nothing of the program under test.  Every matrix product runs at
``Precision.HIGHEST``, because a float32 product on a TPU otherwise rounds
its inputs to bfloat16.

The block is the one the configuration file states, departures included:

* embedding lookup, then per layer a pre-norm attention block and a
  pre-norm MLP block, each added to the residual stream;
* RMSNorm ``x / sqrt(mean(x^2) + eps) * (1 + s)`` with the stored offset
  ``s`` initialised to zero;
* grouped-query attention: query head ``h`` reads key/value head
  ``h // (H / kv)``; rotary embedding on the two halves of each head
  (``rotate_half``), causal softmax scaled by ``1/sqrt(head_dim)``;
* MLP ``gelu_tanh(h W_gate) W_down`` or ``(silu(h W_gate) * h W_up) W_down``;
* an untied output head and the mean cross-entropy over every position;
* the gradient clipped to a global norm, then AdamW with bias correction
  and decoupled weight decay on every parameter.

Attention runs query block by query block and each layer is rematerialised
in the backward pass, so that the reference fits beside nothing else on one
chip at the timed sizes.  Neither changes the arithmetic.

``matmul="fp8"`` makes the control of the correctness check: every weight
projection (attention, MLP, head), forward and backward, multiplies inputs
rounded to float8 e4m3 under one scale per tensor, accumulating in float32.
That is the nearest precision below the bfloat16 inputs that a float32
product gets on a TPU at JAX's default precision.

The weights come from :func:`init_params` and a seed, in this module's own
layout.  :data:`LAYOUT` says how they are stacked into the program's tree,
which is the only thing this module knows of the program.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512

#: program leaf path -> the reference leaves it is made of, stacked on a new
#: axis before the last one when there are several (k and v, gate and up).
LAYOUT = {
    ("embed",): ("embed",),
    ("final_ln",): ("final_ln",),
    ("unembed",): ("unembed",),
    ("units", "b0", "attn", "ln"): ("ln1",),
    ("units", "b0", "attn", "wq"): ("wq",),
    ("units", "b0", "attn", "wkv"): ("wk", "wv"),
    ("units", "b0", "attn", "wo"): ("wo",),
    ("units", "b0", "ffn", "ln"): ("ln2",),
    ("units", "b0", "ffn", "w_in"): ("w_gate", "w_up"),
    ("units", "b0", "ffn", "w_out"): ("w_down",),
}
#: program subtrees whose leaves are stacked over layers
LAYERED = ("units",)


def gated(cfg: dict) -> bool:
    return cfg["hidden_act"] in ("silu", "swiglu")


def shapes(cfg: dict) -> dict:
    """Shape of every reference leaf; layer leaves lead with the depth."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "embed": (V, d), "final_ln": (d,), "unembed": (d, V),
        "ln1": (L, d), "wq": (L, d, H * hd), "wk": (L, d, kv * hd),
        "wv": (L, d, kv * hd), "wo": (L, H * hd, d), "ln2": (L, d),
        "w_gate": (L, d, ff), "w_down": (L, ff, d),
    }
    if gated(cfg):
        out["w_up"] = (L, d, ff)
    return out


def init_params(cfg: dict, key) -> dict:
    """Weights from a key: normal with the published ``initializer_range``,
    the output projections scaled down by ``sqrt(2 * depth)``, norm offsets
    zero.  Each leaf draws from its own fold of the key, so a leaf's values
    do not depend on which other leaves exist or on how they are sharded."""
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2.0 * cfg["num_hidden_layers"])
    params = {}
    for name, shape in shapes(cfg).items():
        if name in ("ln1", "ln2", "final_ln"):
            params[name] = jnp.zeros(shape, jnp.float32)
            continue
        s = out_std if name in ("wo", "w_down") else std
        leaf_key = jax.random.fold_in(key, zlib.crc32(name.encode()))
        params[name] = s * jax.random.normal(leaf_key, shape, jnp.float32)
    return params


def pack(ref: dict) -> dict:
    """The reference tree rearranged into the program's tree (a reshape
    only: linear, so norms of packed gradients are norms of these)."""
    out: dict = {}
    for path, names in LAYOUT.items():
        leaves = [ref[n] for n in names if n in ref]
        leaf = leaves[0] if len(names) == 1 else jnp.stack(leaves, axis=-2)
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return out


def leaf_names(cfg: dict) -> list[str]:
    """Names of the compared leaves: one per program leaf and layer."""
    names = []
    for path in LAYOUT:
        base = "/".join(path)
        if path[0] in LAYERED:
            names += [f"{base}[{l}]" for l in range(cfg["num_hidden_layers"])]
        else:
            names.append(base)
    return names


def leaf_sq_norms(cfg: dict, ref: dict) -> jax.Array:
    """Squared norm of each compared leaf (order of :func:`leaf_names`),
    from a tree in the reference layout."""
    out = []
    for path, names in LAYOUT.items():
        parts = [ref[n] for n in names if n in ref]
        if path[0] in LAYERED:
            sq = sum(jnp.sum(jnp.square(p), axis=tuple(range(1, p.ndim)))
                     for p in parts)
            out += [sq[l] for l in range(cfg["num_hidden_layers"])]
        else:
            out.append(sum(jnp.sum(jnp.square(p)) for p in parts))
    return jnp.stack(out)


# ---------------------------------------------------------------------------
# Weight projections
# ---------------------------------------------------------------------------

def _dot(a, b):
    """``a @ b`` contracting a's last axis with b's first, full float32."""
    return jnp.tensordot(a, b, axes=1, precision=HIGHEST)


def _fp8(x):
    """``x`` rounded to float8 e4m3 under one scale (max |x| to 448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


@jax.custom_vjp
def _dot_fp8(a, b):
    return _dot(_fp8(a), _fp8(b))


def _dot_fp8_fwd(a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _dot(qa, qb), (qa, qb)


def _dot_fp8_bwd(res, g):
    qa, qb = res
    qg = _fp8(g)
    da = jnp.tensordot(qg, qb, axes=((g.ndim - 1,), (1,)),
                       precision=HIGHEST)
    lead = tuple(range(qa.ndim - 1))
    db = jnp.tensordot(qa, qg, axes=(lead, lead), precision=HIGHEST)
    return da, db


_dot_fp8.defvjp(_dot_fp8_fwd, _dot_fp8_bwd)
MATMULS = {"float32": _dot, "fp8": _dot_fp8}


# ---------------------------------------------------------------------------
# Forward pass and loss
# ---------------------------------------------------------------------------

def _rms_norm(x, s, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + s)


def _rope(x, theta):
    """x: (B, T, n, hd); positions 0..T-1; rotate_half convention."""
    T, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA softmax attention, one query block at a time."""
    B, T, H, hd = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    qb = min(Q_BLOCK, T)
    n = T // qb
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        i, qi = args                                   # qi: (B, qb, H, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                       precision=HIGHEST) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    blocks = q.reshape(B, n, qb, H, hd).swapaxes(0, 1)
    o = lax.map(block, (jnp.arange(n), blocks))        # (n, B, qb, H, hd)
    return o.swapaxes(0, 1).reshape(B, T, H * hd)


def _layer(cfg, x, p, mm):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    H, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    B, T, _ = x.shape
    h = _rms_norm(x, p["ln1"], eps)
    q = mm(h, p["wq"]).reshape(B, T, H, hd)
    k = mm(h, p["wk"]).reshape(B, T, kv, hd)
    v = mm(h, p["wv"]).reshape(B, T, kv, hd)
    o = _attention(_rope(q, theta), _rope(k, theta), v)
    x = x + mm(o, p["wo"])
    h = _rms_norm(x, p["ln2"], eps)
    g = mm(h, p["w_gate"])
    if gated(cfg):
        a = jax.nn.silu(g) * mm(h, p["w_up"])
    else:
        a = jax.nn.gelu(g, approximate=True)
    return x + mm(a, p["w_down"])


def loss(cfg: dict, params: dict, tokens: jax.Array, *,
         matmul: str = "float32") -> jax.Array:
    """Mean next-token cross-entropy over every position of every row.
    tokens: (B, T + 1) int32."""
    mm = MATMULS[matmul]
    ids, labels = tokens[:, :-1], tokens[:, 1:]
    x = jnp.take(params["embed"], ids, axis=0)
    layer = jax.checkpoint(lambda x, p: _layer(cfg, x, p, mm))
    layer_keys = [k for k in params if params[k].ndim and k not in
                  ("embed", "final_ln", "unembed")]
    for l in range(cfg["num_hidden_layers"]):
        x = layer(x, {k: params[k][l] for k in layer_keys})
    x = _rms_norm(x, params["final_ln"], cfg["rms_norm_eps"])
    logits = mm(x, params["unembed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# One train step: gradient, global-norm clip, AdamW
# ---------------------------------------------------------------------------

def train_step(cfg: dict, opt: dict, params, m, v, t, tokens, *,
               gather=lambda p: p, matmul: str = "float32"):
    """One step.  Returns ``(params, m, v, loss, gnorm, grad_sq_norms)``:
    the pre-clip global gradient norm, and the squared norm of each
    compared leaf of the clipped gradient (:func:`leaf_sq_norms`).  ``t`` is
    the 1-based step number; ``gather`` places the weights for the forward
    pass (the identity on one device); ``matmul`` names the projections'
    precision (:data:`MATMULS`)."""
    lval, grads = jax.value_and_grad(
        lambda p: loss(cfg, gather(p), tokens, matmul=matmul))(params)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]
    tf = jnp.asarray(t, jnp.float32)
    c1, c2 = 1.0 - b1 ** tf, 1.0 - b2 ** tf

    def upd(p, g, m_, v_):
        m_n = b1 * m_ + (1.0 - b1) * g
        v_n = b2 * v_ + (1.0 - b2) * g * g
        step = (m_n / c1) / (jnp.sqrt(v_n / c2) + eps)
        return p - lr * (step + wd * p), m_n, v_n

    new = {k: upd(params[k], grads[k], m[k], v[k]) for k in params}
    return ({k: o[0] for k, o in new.items()},
            {k: o[1] for k, o in new.items()},
            {k: o[2] for k, o in new.items()}, lval, gnorm,
            leaf_sq_norms(cfg, grads))
