"""The mapping from a configuration file's published keys to the program's
``ModelConfig`` (``benchmark/published.py``), as the training driver
calls it: the benchmark's files map to the model they always ran, an
expert configuration maps to the program's expert layer, and a key the
program cannot express raises and names the key."""

import json

import numpy as np
import pytest

from benchharness import HERE, ROOT, cpu_devices, load

from repro.configs.base import ModelConfig, MoESpec

# Qwen3-30B-A3B's config.json (huggingface.co/Qwen/Qwen3-30B-A3B), its
# keys as published; the token ids and the version enter no mapping.
QWEN3_30B_A3B = {
    "architectures": ["Qwen3MoeForCausalLM"], "attention_bias": False,
    "attention_dropout": 0.0, "bos_token_id": 151643,
    "decoder_sparse_step": 1, "eos_token_id": 151645, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "initializer_range": 0.02,
    "intermediate_size": 6144, "max_position_embeddings": 40960,
    "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "qwen3_moe", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "output_router_logits": False,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000.0,
    "router_aux_loss_coef": 0.001, "sliding_window": None,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "transformers_version": "4.51.0", "use_cache": True,
    "use_sliding_window": False, "vocab_size": 151936,
    "name": "qwen3-30b-a3b"}


def _file(name):
    path = ROOT / "benchmark" / "configs" / f"{name}.json"
    if not path.is_file():
        path = HERE / f"{name}.json"
    return json.loads(path.read_text())


def _dense(c, act):
    """The ``ModelConfig`` the training driver built before it read
    published keys by name: every file it ran was a dense model."""
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], act=act,
        norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"])


@pytest.mark.parametrize("name,act", [("starcoder2-7b-share1", "gelu"),
                                      ("mistral-nemo-12b-hier2x2", "swiglu"),
                                      ("tiny_dense", "swiglu")])
def test_benchmark_files_map_to_the_model_they_ran(name, act):
    """At the cells' 4096 tokens.  starcoder2's published 4096-token
    window masks nothing there, so its layer runs as full attention."""
    train = load("benchmark/drivers/train.py")
    c = _file(name)
    assert train.model_config(c, 4096) == _dense(c, act)


def test_a_window_shorter_than_the_sequence_stays_a_window():
    train = load("benchmark/drivers/train.py")
    c = _file("starcoder2-7b-share1")
    for seq_len in (4097, None):
        mc = train.model_config(c, seq_len)
        assert (mc.pattern, mc.window) == (("local",), 4096)
    assert train.model_config(dict(c, sliding_window=None), None) == \
        _dense(c, "gelu")


def test_qwen3_moe_maps_to_the_programs_expert_layer():
    train = load("benchmark/drivers/train.py")
    mc = train.model_config(QWEN3_30B_A3B, 4096)
    assert mc.family == "moe"
    assert mc.moe == MoESpec(128, 8, 768)
    assert mc.qk_norm
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.n_kv, mc.head_dim,
            mc.vocab, mc.act, mc.norm_eps, mc.rope_theta) == \
        (48, 2048, 32, 4, 128, 151936, "swiglu", 1e-06, 1000000.0)
    assert (mc.pattern, mc.window) == (("attn",), None)
    # a Mixtral-style file names its experts num_local_experts and gives
    # their width as intermediate_size
    mixtral = {k: v for k, v in QWEN3_30B_A3B.items()
               if k not in ("num_experts", "moe_intermediate_size",
                            "norm_topk_prob", "decoder_sparse_step",
                            "mlp_only_layers")}
    mixtral.update(model_type="mixtral", num_local_experts=8,
                   num_experts_per_tok=2, intermediate_size=14336)
    mc = train.model_config(mixtral, 4096)
    assert mc.moe == MoESpec(8, 2, 14336) and not mc.qk_norm


@pytest.mark.parametrize("key,value", [
    ("num_shared_experts", 1),           # a key the program has no field for
    ("shared_expert_intermediate_size", 512),
    ("mlp_only_layers", [0]),            # a dense layer among expert layers
    ("decoder_sparse_step", 2),
    ("norm_topk_prob", False),           # a gate not renormalised
    ("attention_bias", True),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("output_router_logits", True),      # the auxiliary loss in the loss
    ("model_type", "deepseek_v3"),
    ("hidden_act", "relu"),
    ("layer_types", ["linear_attention"] * 48),
])
def test_a_key_the_program_cannot_express_raises(key, value):
    train = load("benchmark/drivers/train.py")
    with pytest.raises(ValueError, match=key):
        train.model_config(dict(QWEN3_30B_A3B, **{key: value}), 4096)


def test_gelu_experts_raise():
    """The program's experts are gated: an ungated activation in an expert
    layer would leave half of each expert's input weights unused."""
    train = load("benchmark/drivers/train.py")
    with pytest.raises(ValueError, match="hidden_act"):
        train.model_config(
            dict(QWEN3_30B_A3B, hidden_act="gelu_pytorch_tanh"), 4096)


def test_tiny_moe_steps_through_the_drivers_mapping():
    """The tiny expert configuration goes through the driver's mapping
    into the program's train step, as a cell's would, and one step on the
    CPU gives a finite loss and gradient norm and moves the weights."""
    import jax
    import jax.numpy as jnp

    from repro.core.topology import MeshTopology
    from repro.runtime.steps import make_train_step
    from repro.substrate.compat import make_mesh

    train = load("benchmark/drivers/train.py")
    tokens = load("benchmark/tokens.py")
    config = _file("tiny_moe")
    traffic = {"kind": "train", "seq_len": 64, "global_batch": 2,
               "zipf_a": 1.3, "motif_len": 8, "motif_prob": 0.5}
    mc = train.model_config(config, traffic["seq_len"])
    assert mc.moe == MoESpec(4, 2, 32) and mc.qk_norm
    axes = config["mesh"]
    mesh = make_mesh(tuple(axes.values()), tuple(axes),
                     devices=cpu_devices(1))
    opt = config["optimizer"]
    bundle = make_train_step(
        mc, MeshTopology(dict(axes)), mesh, mode=config["mode"],
        lr=opt["lr"], weight_decay=opt["weight_decay"], clip=opt["clip"],
        compute_dtype=jnp.float32)
    state = bundle.init_state()
    start = jax.tree.map(np.asarray, state["params"])
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(start)[0]]
    assert any("moe" in p for p in paths)
    batch = tokens.batch(traffic, config["vocab_size"], 2 ** 31 + 5, 0)
    state, met = jax.jit(bundle.fn)(state, {"tokens": jnp.asarray(batch)})
    assert np.isfinite(float(met["loss"])) and float(met["loss"]) > 0
    assert np.isfinite(float(met["gnorm"])) and float(met["gnorm"]) > 0
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                         state["params"], start)
    assert min(jax.tree.leaves(moved)) > 0
