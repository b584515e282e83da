"""Readings that set a training cell's correctness limits, on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds a,b,... \
        --control-seeds x,y,z [--out <file.json>]

In one process, for each seed: the program's first steps (as a run makes
them) and the plain reference on the same weights and rows, compared as a
run compares them.  On the control seeds also:

* the controls: the program's own bfloat16 path (``bf16``:
  ``compute_dtype`` bfloat16, the nearest precision below the
  configuration's float32), and the reference put in the program's place
  with its weight projections in float8 (``fp8``), the precision below the
  bfloat16 inputs a float32 product gets on a TPU at JAX's default
  precision;
* the faults, planted in the reference put in the program's place: half of
  each batch left out (half the rows, or half the positions of a
  one-row batch), and on a cell over several chips each chip's rows alone
  (the exchange left out).

Besides the numbers a run compares, each comparison gives the first step's
alone (``loss1_gap``, ``gnorm1_gap``), which later steps do not amplify.
A step that returns its state unchanged reads ``update_gap`` = 1 by the
measure's definition and needs no run.  The lower reading of a number is
its largest over the program's seeds; its upper reading the least of each
control's (where it reads three times the lower or more) and of each
fault's that reads ten times the lower or more (three times for the state
left unchanged).  The limit proposed lies between them, nearer the upper:
``lower * (upper / lower) ** 0.7``, rounded down to two digits.  A number
with no upper reading gets no limit, and a cell whose limits file leaves
it out does not compare it.  The runs of the benchmark never run this.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT / "src"), str(HERE)]

# JAX reads the compilation cache's place when it is imported
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
import run  # noqa: E402  (benchmark/run.py)

train = run.load_module(HERE / "drivers" / "train.py")


def numbers(r, ref) -> dict:
    """What a run compares, and the first step's gaps alone."""
    return {**train.compare(r, ref),
            "loss1_gap": abs(r.loss[0] - ref.loss[0]) / abs(ref.loss[0]),
            "gnorm1_gap": abs(r.gnorm[0] - ref.gnorm[0]) / abs(ref.gnorm[0])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    devices = run.find_devices(cell["chips"])
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    config, traffic = cell["config"], cell["traffic"]
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = [int(s) for s in args.control_seeds.split(",")]
    prog = train.Program(config, traffic, devices)
    bf16 = train.Program(config, traffic, devices, compute_dtype=jnp.bfloat16)
    B = traffic["global_batch"]
    faults = {"half": ({"rows": slice(0, B // 2)} if B >= 2
                       else {"half_tokens": True})}
    if cell["chips"] > 1:
        faults["solo"] = {"rows": slice(0, B // cell["chips"])}

    records = []
    for seed in dict.fromkeys(seeds + control_seeds):
        t0 = time.monotonic()
        rec = {"seed": seed}
        pool = prog.batches(seed)
        state, r_prog = prog.first_steps(seed, pool)
        del state
        r_ctrl = {}
        if seed in control_seeds:
            state, r_ctrl["bf16"] = bf16.first_steps(seed, pool)
            del state
        del pool
        r_ref = train.reference_readings(config, traffic, seed, prog.devices)
        rec["program_loss"] = r_prog.loss
        rec["program_gnorm"] = r_prog.gnorm
        rec["reference_loss"] = r_ref.loss
        rec["reference_gnorm"] = r_ref.gnorm
        rec["leaves"] = len(r_ref.grad)
        rec["leaves_left_out"] = int(
            (r_ref.grad < 1e-3 * np.median(r_ref.grad)).sum())
        if seed in seeds:
            rec["program"] = numbers(r_prog, r_ref)
        if seed in control_seeds:
            r_ctrl["fp8"] = train.reference_readings(
                config, traffic, seed, prog.devices, matmul="fp8")
            for name, r in r_ctrl.items():
                rec[f"control_{name}"] = numbers(r, r_ref)
                rec[f"control_{name}_loss"] = r.loss
                rec[f"control_{name}_gnorm"] = r.gnorm
            for name, kw in faults.items():
                r_f = train.reference_readings(config, traffic, seed,
                                               prog.devices, **kw)
                rec[f"fault_{name}"] = numbers(r_f, r_ref)
        rec["seconds"] = time.monotonic() - t0
        print(json.dumps(rec), flush=True)
        records.append(rec)

    controls = ["control_bf16", "control_fp8"]
    summary = {}
    for k in records[0].get("program") or records[0][controls[0]]:
        lower = max(r["program"][k] for r in records if "program" in r)
        summary[k] = {"lower": lower}
        cands = {}
        for c in controls:
            v = min(r[c][k] for r in records if c in r)
            summary[k][c] = v
            if v >= 3 * lower:
                cands[c] = v
        for f in faults:
            v = min(r[f"fault_{f}"][k] for r in records if f"fault_{f}" in r)
            if v >= 10 * lower:
                cands[f"fault_{f}"] = v
        if k == "update_gap" and 1.0 >= 3 * lower:
            cands["fault_unchanged"] = 1.0
        summary[k]["upper_candidates"] = cands
        if cands and lower > 0:
            raw = lower * (min(cands.values()) / lower) ** 0.7
            step = 10.0 ** (math.floor(math.log10(raw)) - 1)
            summary[k]["limit"] = float(f"{math.floor(raw / step) * step:.2g}")
    out = {"cell": args.workload, "records": records, "summary": summary}
    print(json.dumps(summary, indent=1))
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
