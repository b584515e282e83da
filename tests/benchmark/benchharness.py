"""Helpers shared by the benchmark's CPU tests: load the harness's modules
by path (the benchmark is a directory of scripts, not a package) and build
a cell of a small configuration."""

import copy
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def load(rel: str):
    path = ROOT / rel
    name = "benchtest_" + rel.replace("/", "_").replace(".", "_") \
        .replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def tiny_cell(*, chips: int = 1, limits_of: str = "sc2-train-4k-1chip"):
    """A cell of the tiny configuration under a training mix: on one device
    a batch of 2 rows, on 2x2 a batch of 8.  Its limits are those of the
    named benchmark cell."""
    config = json.loads((HERE / "tiny_dense.json").read_text())
    batch = 2
    if chips == 4:
        config["mesh"] = {"pod": 2, "data": 2, "model": 1}
        batch = 8
    traffic = {"kind": "train", "seq_len": 64, "global_batch": batch,
               "zipf_a": 1.3, "motif_len": 8, "motif_prob": 0.5}
    limits = json.loads((ROOT / "benchmark" / "limits" /
                         f"{limits_of}.json").read_text())["limits"]
    return {"name": "tiny", "chips": chips, "config": config,
            "traffic": traffic, "limits": copy.deepcopy(limits),
            "end_to_end": [], "per_layer": []}


def cpu_devices(n: int):
    import jax
    devs = jax.devices("cpu")
    assert len(devs) >= n, f"need {n} CPU devices, have {len(devs)}"
    return devs[:n]
