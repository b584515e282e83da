"""The benchmark's definition: every cell resolves its files by name, the
token generator is a pure function of the seed, and the FLOPs count agrees
with the program's own parameter count."""

import json
import re

import numpy as np
import pytest

from benchharness import ROOT, load

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    run = load("benchmark/run.py")
    c = run.load_cell(cell)
    assert (ROOT / "benchmark" / "drivers" /
            f"{c['traffic']['kind']}.py").is_file()
    assert (ROOT / "benchmark" / "references" /
            f"{c['config']['reference']}.py").is_file()
    assert c["config"]["mesh"] and \
        np.prod(list(c["config"]["mesh"].values())) == c["chips"]
    assert {"loss_gap", "update_gap"} <= set(c["limits"]) <= {
        "loss_gap", "gnorm_gap", "grad_gap", "update_gap"}
    assert all(0 < v < 1 for v in c["limits"].values())
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    for m in c["per_layer"]:
        reader = load(f"benchmark/metrics/{m['name']}.py")
        assert callable(reader.read)


def test_benchmark_json_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51
    seen = set()
    for entry in (BENCH["configs"] + BENCH["workloads"] +
                  BENCH["end_to_end"] + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        assert entry["name"] not in seen
        seen.add(entry["name"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        changed = {k for k, v in cfg["published"].items()
                   if k in cfg and cfg[k] != v}
        assert changed == set(c["reduced"]), (c["name"], changed)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    moves = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in moves
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_batches_are_a_function_of_the_seed():
    tokens = load("benchmark/tokens.py")
    traffic = json.loads(
        (ROOT / "benchmark" / "traffic" / "train-4k-b4.json").read_text())
    traffic = dict(traffic, seq_len=256)
    seed = 2 ** 31 + 977
    a = tokens.pool(traffic, 16384, seed, 4)
    b = tokens.pool(traffic, 16384, seed, 4)
    c = tokens.pool(traffic, 16384, seed + 1, 4)
    assert len(a) == 4
    for x, y, z in zip(a, b, c):
        assert x.shape == (traffic["global_batch"], 257)
        assert x.dtype == np.int32
        assert x.min() >= 0 and x.max() < 16384
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x, z)
    rows = np.concatenate(a)
    assert len({r.tobytes() for r in rows}) == len(rows)
    w = tokens.seed_words(seed)
    assert w.dtype == np.uint32 and w.shape == (2,)
    np.testing.assert_array_equal(w, tokens.seed_words(seed))


# A 4-layer pipeline stage of Qwen3-30B-A3B at its published widths, with
# an eighth of its vocabulary (151936 / 8).
QWEN3_STAGE = {
    "name": "qwen3-30b-a3b-stage", "model_type": "qwen3_moe",
    "hidden_size": 2048, "intermediate_size": 6144,
    "moe_intermediate_size": 768, "num_experts": 128,
    "num_experts_per_tok": 8, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "num_hidden_layers": 4, "vocab_size": 18992, "hidden_act": "silu",
    "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "tie_word_embeddings": False, "sliding_window": None,
    "use_sliding_window": False, "max_window_layers": 48}


def _config(name):
    if name == QWEN3_STAGE["name"]:
        return dict(QWEN3_STAGE)
    path = ROOT / "benchmark" / "configs" / f"{name}.json"
    if not path.is_file():
        path = ROOT / "tests" / "benchmark" / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("name", ["starcoder2-7b-share1",
                                  "mistral-nemo-12b-hier2x2", "tiny_moe",
                                  "qwen3-30b-a3b-stage"])
def test_flops_parameter_term_matches_param_count(name):
    """``ModelConfig.active_param_count`` (all parameters for a dense
    model; for an expert model those of the experts a token goes to, and
    the router) counts the embedding twice when untied (lookup and head)
    and four d-vectors of norm per layer; net of the lookup and the norms
    it is the FLOPs count's matrix parameters."""
    flops = load("benchmark/flops.py")
    train = load("benchmark/drivers/train.py")
    cfg = _config(name)
    mc = train.model_config(cfg, 4096)
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    assert flops.matmul_params(cfg) == \
        mc.active_param_count() - V * d - 4 * d * L
    per_token = flops.train_flops_per_token(cfg, 4096)
    assert per_token == 6 * flops.matmul_params(cfg) + \
        12 * L * cfg["num_attention_heads"] * cfg["head_dim"] * 4096


@pytest.mark.parametrize("name,per_token", [
    ("starcoder2-7b-share1", 1868562432.0),
    ("mistral-nemo-12b-hier2x2", 4177526784.0),
    # the router's 2048 x 128 per layer on top of the dense formula's
    # 2,397,634,560 with intermediate_size 6144 = 8 x 768
    ("qwen3-30b-a3b-stage", 2403926016.0),
])
def test_flops_per_token(name, per_token):
    """The count each cell's ``train.mfu`` divides by, as a literal: the
    two configuration files give what they gave before layers were
    counted by kind."""
    flops = load("benchmark/flops.py")
    assert flops.train_flops_per_token(_config(name), 4096) == per_token


def test_flops_count_the_experts_a_token_goes_to():
    """A Mixtral-shaped layer (8 experts of intermediate_size 14336, top 2)
    multiplies by two experts and the router, not by one MLP of
    intermediate_size; a layer ``mlp_only_layers`` lists stays dense."""
    flops = load("benchmark/flops.py")
    d, ff = 4096, 14336
    mixtral = {"hidden_size": d, "intermediate_size": ff,
               "num_local_experts": 8, "num_experts_per_tok": 2,
               "num_attention_heads": 32, "num_key_value_heads": 8,
               "head_dim": 128, "num_hidden_layers": 1, "vocab_size": 1,
               "hidden_act": "silu"}
    attn = d * (32 + 16) * 128 + 32 * 128 * d
    assert flops.matmul_params(mixtral) == attn + 2 * 3 * d * ff + d * 8 + d
    dense = dict(mixtral, num_local_experts=0)
    assert flops.matmul_params(dense) == attn + 3 * d * ff + d
    stage = dict(QWEN3_STAGE, mlp_only_layers=[0])
    assert flops.matmul_params(stage) - flops.matmul_params(QWEN3_STAGE) == \
        3 * 2048 * 6144 - (8 * 3 * 2048 * 768 + 2048 * 128)


def test_flops_cut_attention_to_the_window_of_windowed_layers():
    """Only layers the configuration marks as windowed see fewer keys than
    the sequence: every layer under a bare ``sliding_window``; none while
    ``use_sliding_window`` is false; those ``layer_types`` marks."""
    flops = load("benchmark/flops.py")
    cfg = dict(QWEN3_STAGE, sliding_window=1024)
    per_key = 12 * 32 * 128
    base = 6.0 * flops.matmul_params(cfg)
    assert flops.train_flops_per_token(cfg, 4096) == base + 4 * per_key * 4096
    on = dict(cfg, use_sliding_window=True, max_window_layers=2)
    assert flops.train_flops_per_token(on, 4096) == \
        base + per_key * (2 * 4096 + 2 * 1024)
    typed = dict(cfg, layer_types=["sliding_attention", "full_attention"] * 2)
    assert flops.train_flops_per_token(typed, 4096) == \
        base + per_key * (2 * 4096 + 2 * 1024)
    bare = {k: v for k, v in cfg.items()
            if k not in ("use_sliding_window", "max_window_layers")}
    assert flops.train_flops_per_token(bare, 4096) == base + 4 * per_key * 1024
    assert flops.train_flops_per_token(bare, 512) == \
        6.0 * flops.matmul_params(bare) + 4 * per_key * 512


def test_peaks_table_names_its_source():
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["peak_flops_bf16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_peak_memory_counts_the_reservation_on_the_fullest_chip():
    """The step's temporaries sit in the allocator's reservation, not in its
    buffers in use; the peak is the larger of the two, on the fullest chip."""
    train = load("benchmark/drivers/train.py")
    devices = [_Device({"peak_bytes_in_use": 4, "peak_bytes_reserved": 8}),
               _Device({"peak_bytes_in_use": 9, "peak_bytes_reserved": 5}),
               _Device(None)]
    assert train.peak_bytes(devices) == 9
    assert train.peak_bytes(devices[:1]) == 8
    assert train.peak_bytes(devices[2:]) == 0
