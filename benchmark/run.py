"""Run one cell of the benchmark on the accelerator and print its result.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's configuration file, its traffic mix
(``benchmark/traffic/<traffic>.json``), the driver for the mix's kind
(``benchmark/drivers/<kind>.py``), the limits of its correctness check
(``benchmark/limits/<cell>.json``) and, with ``--trace 1``, one reader per
per-layer metric (``benchmark/metrics/<metric>.py``).  Adding a cell, a mix
or a metric adds files and entries; it edits none.

The run needs a TPU with as many chips as the cell asks for; with none it
exits non-zero and prints no result.  JAX's compilation cache is kept in
``.jax_cache/`` at the root of the checkout.  The last line of standard
output is the result as one JSON object; the numbers the correctness check
compared, each beside its limit, are the last lines of standard error and
the last key of that object.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "benchmark"
TRACE_DIR = ROOT / ".bench_trace"
GIB = 2 ** 30


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """Everything a run of cell ``name`` needs, found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; cells: "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = root / "benchmark"

    def applies(m):
        return name in m.get("workloads", [name])

    return {
        "name": name,
        "chips": w["chips"],
        "config": json.loads((root / cfg_entry["file"]).read_text()),
        "traffic": json.loads(
            (here / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads(
            (here / "limits" / f"{name}.json").read_text())["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def find_devices(chips: int):
    """The first ``chips`` TPU devices; exits when there are fewer."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} "
                         "devices only")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


class Compiles:
    """Counts backend compilations through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kwargs) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1


class View:
    """What a per-layer metric reader reads: the run's raw numbers, the
    reduced trace (``None`` without one), the cell and the peak table's
    entry for the device."""

    def __init__(self, cell, raw, trace, peak):
        self.cell, self.raw, self.trace, self.peak = cell, raw, trace, peak


def end_to_end(raw: dict) -> dict:
    return {
        "train_tokens_per_s": raw["tokens"] / raw["window_s"],
        "peak_hbm_gib": raw["memory_peak_bytes"] / GIB,
        "setup_s": raw["setup_s"],
    }


def result_line(cell: dict, raw: dict, trace_on: bool) -> dict:
    devices = raw["devices"]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": raw["memory_peak_bytes"]}
    out = {"correct": raw["correct"], "attempted": raw["attempted"],
           "failed": raw["failed"]}
    metrics = {}
    if trace_on:
        trace = raw["trace"]
        peaks = json.loads((HERE / "peaks.json").read_text())
        if device["kind"] not in peaks:
            raise SystemExit(f"no peaks for device kind {device['kind']!r} "
                             "in benchmark/peaks.json")
        view = View(cell, raw, trace, peaks[device["kind"]])
        for m in cell["per_layer"]:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace["busy_mean_ns"] / 1e9
        device["window_s"] = trace["window_ns"] / 1e9
        out["breakdown"] = trace["breakdown"]
    else:
        values = end_to_end(raw)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = raw["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    # the compilation cache sits at a fixed place inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    devices = find_devices(cell["chips"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()

    driver = load_module(HERE / "drivers" / f"{cell['traffic']['kind']}.py")
    raw = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace_dir=TRACE_DIR if args.trace else None,
                     devices=devices, t_start=T_START, compiles=compiles)
    if args.trace:
        trace_mod = load_module(HERE / "trace.py")
        trace = trace_mod.extract(trace_mod.latest_xplane(str(TRACE_DIR)),
                                  raw["hlo_text"])
        raw["trace"] = trace_mod.reduce(trace)
    line = result_line(cell, raw, bool(args.trace))
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
