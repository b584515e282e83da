"""CLI: ``python -m repro.bench [--quick] [--out BENCH_collectives.json]``.

Runs the matrix-driven collective sweep in-process on forced host CPU
devices, cross-checks every measured config against the plans.py traffic
model (any mismatch exits non-zero) and writes the schema-versioned JSON
artifact.  ``--csv`` additionally prints ``name,us_per_call,derived``
rows.

``--emit-tuning-table`` instead FOLDS an existing report (``--bench``,
default the committed ``BENCH_collectives.json``) into the scheme-selection
table ``scheme="auto"`` dispatches through (``--table-out``, default
``TUNING_default.json``) — no re-measurement.  The fold is self-checked:
every emitted winner must hold the best pooled median of the very report it
came from (``repro.bench.validate.tuning_table_checks``), so a broken fold
can never reach dispatch.

Device forcing happens HERE, before the jax backend initializes — which is
why the heavy imports live inside ``main``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _emit_tuning_table(bench_path: str, table_out: str) -> int:
    from repro.bench.validate import tuning_table_checks
    from repro.comm.tuning import TuningTable

    with open(bench_path) as f:
        rep = json.load(f)
    table = TuningTable.from_bench_report(rep, source_name=bench_path)
    bad = [ch for ch in tuning_table_checks(table, rep) if not ch.ok]
    if bad:
        print(f"repro.bench: tuning-table fold FAILED {len(bad)} winner "
              "cross-check(s) against its own report:", file=sys.stderr)
        for ch in bad:
            print(f"  {ch.name}: expected {ch.expected}, measured "
                  f"{ch.measured} ({ch.note})", file=sys.stderr)
        return 1
    table.save(table_out)
    measured = sum(1 for e in table.entries if e.source == "measured")
    print(f"repro.bench: wrote {table_out} ({measured} measured entries "
          f"over {len(table.signatures())} topology signatures, folded "
          f"from {bench_path})", file=sys.stderr)
    return 0


def _force_devices(n: int | None) -> None:
    """``--devices N`` overrides any inherited force flag (XLA honors the
    last duplicate); the default defers to an already-present flag (CI
    pins its own count)."""
    if n is None:
        from repro.substrate import ensure_host_device_count
        ensure_host_device_count(8)
    else:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.bench",
        description="matrix-driven collective benchmarks with "
                    "traffic-model cross-checks")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep: one message size, 5 reps")
    ap.add_argument("--out", default="BENCH_collectives.json",
                    help="JSON artifact path (default %(default)s)")
    ap.add_argument("--csv", action="store_true",
                    help="also print name,us_per_call,derived rows "
                         "(no header)")
    ap.add_argument("--devices", type=int, default=None,
                    help="force this many host devices (default: respect "
                         "XLA_FLAGS, else 8)")
    ap.add_argument("--max-devices", type=int, default=8,
                    help="cap the topology matrix (default %(default)s)")
    ap.add_argument("--families", default=None,
                    help="comma list: allgather,broadcast,psum,"
                         "reduce_scatter,allgatherv,alltoall,"
                         "step_time,serving")
    ap.add_argument("--schemes", default=None,
                    help="comma list of registry scheme names (fast "
                         "autotune iteration, e.g. pipelined,hier)")
    ap.add_argument("--elems", default=None,
                    help="comma list of message sizes in elems, overriding "
                         "the quick/full defaults (e.g. 1024,65536)")
    ap.add_argument("--dtypes", default="float32",
                    help="comma list of payload dtypes; non-float32 "
                         "entries sweep only the wire-format-sensitive "
                         "families (allgather, psum) so the tuning table "
                         "can discriminate by dtype "
                         "(e.g. float32,bfloat16; default %(default)s)")
    ap.add_argument("--reps", type=int, default=None,
                    help="timed reps per case (default 30, quick 5)")
    ap.add_argument("--min-rep-s", type=float, default=0.0,
                    help="calibrate an inner loop so every timed rep lasts "
                         "at least this many seconds (smooths per-call "
                         "scheduling jitter on noisy hosts)")
    ap.add_argument("--no-validate", action="store_true",
                    help="skip the traffic-model cross-checks (timing "
                         "only; the JSON then carries no checks)")
    ap.add_argument("--emit-tuning-table", action="store_true",
                    help="fold an existing report (--bench) into the "
                         "scheme='auto' tuning table (--table-out) and "
                         "exit — runs no sweep")
    ap.add_argument("--bench", default="BENCH_collectives.json",
                    help="input report for --emit-tuning-table "
                         "(default %(default)s)")
    ap.add_argument("--table-out", default="TUNING_default.json",
                    help="tuning-table path for --emit-tuning-table "
                         "(default %(default)s)")
    args = ap.parse_args(argv)

    if args.emit_tuning_table:
        return _emit_tuning_table(args.bench, args.table_out)

    _force_devices(args.devices)

    # jax backends initialize on first device query — after the flag above.
    from repro.bench import report, suites
    from repro.bench.validate import BenchValidationError
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    families = tuple(args.families.split(",")) if args.families \
        else suites.FAMILIES
    schemes = tuple(args.schemes.split(",")) if args.schemes else None
    if args.elems:
        elems = tuple(int(e) for e in args.elems.split(","))
    else:
        elems = suites.QUICK_ELEMS if args.quick else suites.FULL_ELEMS
    reps = args.reps if args.reps is not None else (5 if args.quick else 30)
    dtypes = tuple(args.dtypes.split(","))

    cases = suites.build_cases(
        families=families, elems=elems, max_devices=args.max_devices,
        schemes=schemes, dtypes=dtypes,
        on_skip=lambda msg: print(f"repro.bench: {msg}", file=sys.stderr))
    print(f"repro.bench: {len(cases)} cases over "
          f"{len({c.topology for c in cases})} topologies x {elems} elems "
          f"x dtypes {dtypes} (reps={reps})", file=sys.stderr)
    try:
        suite = suites.run_suite(cases, reps=reps,
                                 min_rep_s=args.min_rep_s,
                                 validate=not args.no_validate,
                                 log=lambda s: print(s, file=sys.stderr))
    except BenchValidationError as e:
        print(f"repro.bench: {e}", file=sys.stderr)
        return 1

    rep = report.to_report(suite, quick=args.quick, reps=reps,
                           families=families, elems=elems, dtypes=dtypes)
    report.write_report(rep, args.out)
    if args.csv:
        for row in report.csv_rows(suite):
            print(row)
    ok = rep["validation"]["ok"]
    print(f"repro.bench: wrote {args.out} "
          f"({len(rep['cases'])} cases, validation "
          f"{'OK' if ok else 'FAILED'}, "
          f"{rep['validation']['num_checks']} checks)", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
