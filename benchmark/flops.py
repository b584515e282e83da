"""Model FLOPs of a training step, from the configuration's sizes alone.

The count is the one of Kaplan et al. 2020 ("Scaling Laws for Neural
Language Models", section 2.1) and Chowdhery et al. 2022 ("PaLM", appendix
B): a forward and backward pass cost 6 FLOPs per matrix parameter per token,
and attention adds ``12 * layers * (heads * head_dim) * seq_len`` per token
(the score and value products, forward and backward, with the causal half
not subtracted).  The embedding lookup is a gather and counts nothing; the
output head is a matrix and counts.  Recomputation in the backward pass
counts nothing: these are the FLOPs the model requires, not the FLOPs the
program spends.
"""

from __future__ import annotations


def gated(cfg: dict) -> bool:
    return cfg["hidden_act"] in ("silu", "swiglu")


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply activations: attention and MLP projections
    of every layer, and the output head."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    attn = d * (H + 2 * kv) * hd + H * hd * d
    mlp = (3 if gated(cfg) else 2) * d * ff
    return L * (attn + mlp) + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * seq_len
    return 6.0 * matmul_params(cfg) + attn
