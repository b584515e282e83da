"""The program's ``ModelConfig`` for a configuration file of published keys.

A configuration file keeps the model's published ``config.json`` keys under
their own names (``hidden_size``, ``num_experts``, ...) beside the
benchmark's own bookkeeping (``source``, ``reduced``, ``mesh``, ...).
:func:`from_published` maps every key that shapes the model's equations to
a field of ``repro.configs.base.ModelConfig``, and raises, naming the key,
for any such key it cannot map: a key it does not know, or a value the
program has no field for (a dense layer among expert layers, an attention
bias).  An unmapped key never runs a different model in silence.

The benchmark's plain references import nothing of the program and read
the same file on their own, so ``correct`` still judges this mapping.

Windows follow the published semantics: with ``layer_types``, the layers
marked ``sliding_attention``; else, where ``use_sliding_window`` is given,
only if it is true and from layer ``max_window_layers`` on; else every
layer where ``sliding_window`` is set.  A window at least as long as the
run's sequences masks nothing, so with ``seq_len`` given such layers run
as full attention.
"""

from __future__ import annotations

# The benchmark's bookkeeping and the published file's, which say nothing
# about the model's equations.  ``router_aux_loss_coef`` enters the loss
# only with ``output_router_logits``, which :data:`FIXED` holds false.
DESCRIPTIVE = frozenset({
    "source", "paper", "published", "reduced", "assumed", "deployment",
    "departures", "dtypes", "mesh", "mode", "optimizer", "reference",
    "initializer_range", "max_position_embeddings", "architectures",
    "torch_dtype", "transformers_version", "use_cache", "bos_token_id",
    "eos_token_id", "pad_token_id", "router_aux_loss_coef"})

# Published keys and the ModelConfig field each one sets.
FIELDS = {
    "name": "name", "num_hidden_layers": "n_layers",
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}

# Keys whose published meaning the program has one value of: the value it
# implements.  Any other value raises.
FIXED = {
    "attention_bias": False, "mlp_bias": False, "attention_dropout": 0.0,
    "rope_scaling": None, "router_jitter_noise": 0.0,
    "output_router_logits": False,
    # the gate is a softmax over the chosen experts' logits
    # (``repro.models.moe.route``), the renormalised top-k
    "norm_topk_prob": True,
    # every layer is an expert layer where experts are given
    "decoder_sparse_step": 1, "mlp_only_layers": [],
}

ACTS = {"gelu_pytorch_tanh": "gelu", "silu": "swiglu"}

# model_type -> ModelConfig fields it implies beyond the other keys.
MODEL_TYPES = {
    "qwen3": {"qk_norm": True},
    "qwen3_moe": {"qk_norm": True},
    "mistral": {},
    "mixtral": {},
}

EXPERTS = ("num_experts", "num_local_experts")
WINDOW_KEYS = ("sliding_window", "use_sliding_window", "max_window_layers",
               "layer_types")
EXPERT_KEYS = EXPERTS + ("num_experts_per_tok", "moe_intermediate_size")


def _windows(config: dict) -> list[int | None]:
    """The window of each layer, ``None`` for full attention."""
    n = config["num_hidden_layers"]
    w = config.get("sliding_window")
    if "layer_types" in config:
        kinds = config["layer_types"]
        if set(kinds) - {"full_attention", "sliding_attention"} or \
                len(kinds) != n:
            raise ValueError(f"layer_types {kinds!r}: the program maps "
                              f"{n} entries of full_attention or "
                              "sliding_attention")
        return [w if k == "sliding_attention" else None for k in kinds]
    if w is None:
        return [None] * n
    if "use_sliding_window" in config:
        if not config["use_sliding_window"]:
            return [None] * n
        first = config.get("max_window_layers", 0)
        return [w if i >= first else None for i in range(n)]
    return [w] * n


def from_published(config: dict, seq_len: int | None = None):
    """``ModelConfig`` of a configuration file's published keys.

    ``seq_len`` is the longest sequence the run feeds; windows that cover
    it run as full attention.  Raises ``ValueError``, naming the key, for
    an architecture key the program cannot express."""
    from repro.configs.base import ModelConfig, MoESpec

    known = (DESCRIPTIVE | set(FIELDS) | set(FIXED) | set(EXPERT_KEYS)
             | set(WINDOW_KEYS) | {"hidden_act", "model_type"})
    for key in config:
        if key not in known:
            raise ValueError(f"published key {key!r} has no field in the "
                              "program's ModelConfig")
    for key, value in FIXED.items():
        if key in config and config[key] != value:
            raise ValueError(f"{key} = {config[key]!r}: the program "
                              f"implements only {value!r}")
    act = config["hidden_act"]
    if act not in ACTS:
        raise ValueError(f"hidden_act {act!r}: the program maps "
                          f"{sorted(ACTS)}")
    mtype = config.get("model_type")
    if mtype is not None and mtype not in MODEL_TYPES:
        raise ValueError(f"model_type {mtype!r}: the program maps "
                          f"{sorted(MODEL_TYPES)}")

    fields = {FIELDS[k]: v for k, v in config.items() if k in FIELDS}
    fields.setdefault("head_dim",
                      config["hidden_size"] // config["num_attention_heads"])
    fields.update(MODEL_TYPES.get(mtype, {}), act=ACTS[act], family="dense")

    experts = [k for k in EXPERTS if k in config]
    if len(experts) > 1:
        raise ValueError(f"both {experts[0]} and {experts[1]} are given")
    if experts and config[experts[0]]:
        if ACTS[act] != "swiglu":
            raise ValueError(f"hidden_act {act!r} in an expert layer: the "
                              "program's experts are gated")
        width = config.get("moe_intermediate_size",
                           config["intermediate_size"])
        fields.update(family="moe", moe=MoESpec(
            config[experts[0]], config["num_experts_per_tok"], width))

    kinds = tuple("attn" if w is None or (seq_len is not None and
                                          w >= seq_len) else "local"
                  for w in _windows(config))
    if "local" in kinds:
        fields.update(window=config["sliding_window"],
                      pattern=kinds[:1] if len(set(kinds)) == 1 else kinds)
    return ModelConfig(**fields)
