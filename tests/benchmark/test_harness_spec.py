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


@pytest.mark.parametrize("name", ["starcoder2-7b-share1",
                                  "mistral-nemo-12b-hier2x2"])
def test_flops_parameter_term_matches_param_count(name):
    """``ModelConfig.param_count`` counts the embedding twice when untied
    (lookup and head) and four d-vectors of norm per layer; net of the
    lookup and the norms it is the FLOPs count's matrix parameters."""
    flops = load("benchmark/flops.py")
    train = load("benchmark/drivers/train.py")
    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{name}.json").read_text())
    mc = train.model_config(cfg)
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    assert flops.matmul_params(cfg) == mc.param_count() - V * d - 4 * d * L
    per_token = flops.train_flops_per_token(cfg, 4096)
    assert per_token == 6 * flops.matmul_params(cfg) + \
        12 * L * cfg["num_attention_heads"] * cfg["head_dim"] * 4096


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
