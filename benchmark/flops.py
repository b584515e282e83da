"""Model FLOPs of a training step, from the configuration's sizes alone.

The count is the one of Kaplan et al. 2020 ("Scaling Laws for Neural
Language Models", section 2.1) and Chowdhery et al. 2022 ("PaLM", appendix
B): a forward and backward pass cost 6 FLOPs per matrix parameter per token,
and attention adds ``12 * (heads * head_dim) * seq_len`` per layer and
token (the score and value products, forward and backward, with the causal
half not subtracted), ``seq_len`` cut to the window in a windowed layer.
The embedding lookup is a gather and counts nothing; the output head is a
matrix and counts.  Recomputation in the backward pass counts nothing:
these are the FLOPs the model requires, not the FLOPs the program spends.

Layers count by kind, read from the published keys.  A dense layer's MLP
multiplies by ``intermediate_size``; an expert layer's by the
``num_experts_per_tok`` experts each token goes to, each of
``moe_intermediate_size`` (Mixtral's files, which give
``num_local_experts``, use ``intermediate_size`` for it), and by its
router, ``hidden_size x num_experts``.  A layer is an expert layer unless
``mlp_only_layers`` lists it or ``decoder_sparse_step`` skips it.
Windows follow ``layer_types``, else ``use_sliding_window`` from layer
``max_window_layers`` on, else ``sliding_window`` in every layer.
"""

from __future__ import annotations


def gated(cfg: dict) -> bool:
    return cfg["hidden_act"] in ("silu", "swiglu")


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def experts(cfg: dict) -> int:
    return cfg.get("num_experts") or cfg.get("num_local_experts") or 0


def expert_layer(cfg: dict, i: int) -> bool:
    return experts(cfg) > 0 and i not in cfg.get("mlp_only_layers", ()) \
        and (i + 1) % cfg.get("decoder_sparse_step", 1) == 0


def window(cfg: dict, i: int) -> int | None:
    """Layer ``i``'s attention window, ``None`` where it sees every key."""
    w = cfg.get("sliding_window")
    if "layer_types" in cfg:
        return w if cfg["layer_types"][i] == "sliding_attention" else None
    if "use_sliding_window" in cfg and not (
            cfg["use_sliding_window"] and i >= cfg.get("max_window_layers",
                                                       0)):
        return None
    return w


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply each token's activations: attention and MLP
    (or routed experts and router) of every layer, and the output head."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = head_dim(cfg)
    n_mat = 3 if gated(cfg) else 2
    attn = d * (H + 2 * kv) * hd + H * hd * d
    dense = n_mat * d * cfg["intermediate_size"]
    width = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    routed = cfg.get("num_experts_per_tok", 0) * n_mat * d * width \
        + d * experts(cfg)
    return sum(attn + (routed if expert_layer(cfg, i) else dense)
               for i in range(L)) + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    per_key = 12 * cfg["num_attention_heads"] * head_dim(cfg)
    attn = sum(per_key * min(seq_len, window(cfg, i) or seq_len)
               for i in range(cfg["num_hidden_layers"]))
    return 6.0 * matmul_params(cfg) + attn
