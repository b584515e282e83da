"""Schema-versioned benchmark report (``BENCH_collectives.json``) + the
legacy ``name,us_per_call,derived`` CSV rows.

The JSON is the artifact that seeds the perf trajectory: every later perf
PR appends a measured config to the same schema and diffs against the
previous artifact.  Structure (``repro.bench/v1``):

* top level — ``schema``, environment (jax version / backend / device
  count), the sweep parameters and the topology-matrix labels;
* ``cases[]`` — one record per measured config: identity (family, scheme,
  topology, pods, chips, elems), ``timing`` (median/mean/min/max/iqr us,
  reps, inner), ``traffic`` (the plans.py model), ``hlo`` (bytes parsed
  from the compiled module) and the per-case ``checks``;
* ``cross_checks[]`` — the C1 resident-memory invariants measured across
  schemes;
* ``validation`` — overall verdict (always ``ok: true`` in a written file:
  a mismatch raises before the report is written).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Sequence

from repro.bench import SCHEMA_VERSION
from repro.bench.suites import CaseResult, SuiteResult


def case_record(r: CaseResult) -> dict:
    c = r.case
    serving = None
    if c.family == "serving":
        # open-loop Poisson load model priced by the measured step median:
        # tokens/sec + p50/p99 per-token latency per matrix topology
        # (deterministic given the timing — seeded sim, no wall clock)
        from repro.bench.serving import serving_metrics
        serving = serving_metrics(r.timing.median_us)
    return {
        "name": c.name,
        "csv_name": c.csv_name,
        "family": c.family,
        "scheme": c.scheme,
        "topology": c.topology,
        "pods": c.cluster.pods,
        "chips": c.cluster.chips,
        "elems": c.elems,
        "bytes_per_rank": c.elems * c.elem_bytes,
        "dtype": c.dtype,
        "fast_axes": len(c.cluster.fast_names),
        "populations": list(c.populations) if c.populations else None,
        "timing": r.timing.to_dict(),
        "traffic": dataclasses.asdict(c.traffic),
        "hlo": r.hlo,
        "checks": [ch.to_dict() for ch in r.checks],
        "autotune": r.autotune,
        "serving": serving,
        "ok": all(ch.ok for ch in r.checks),
    }


def copies_per_node(r: CaseResult) -> int:
    """The fixed fig7 'derived' column: how many copies of the FULL result
    a node holds (naive: one per rank; shared: one — paper C1).  The seed
    bench divided by per-rank bytes and printed rank counts instead."""
    c = r.case
    eb = c.elem_bytes
    if c.family in ("allgather", "alltoall"):
        # alltoall: the "full result" is one rank's R*m receive buffer —
        # rank-private in every scheme, so copies_per_node == ranks_per_node
        full = c.cluster.num_devices * c.elems * eb
    elif c.family == "allgatherv":
        full = sum(c.populations) * c.elems * eb
    elif c.family == "reduce_scatter":
        # unit = the node's flat share of the scattered result; the shared
        # window keeps the whole reduced message (num_nodes shares) once
        full = c.elems * eb // c.cluster.pods
    else:                       # broadcast / psum: the message itself
        full = c.elems * eb
    return c.traffic.result_bytes_per_node // full


def csv_rows(suite: SuiteResult) -> list[str]:
    """``name,us_per_call,derived`` rows (the ``--csv`` output)."""
    rows = []
    for r in suite.cases:
        t = r.case.traffic
        derived = (f"slow_bytes={t.slow_bytes};fast_bytes={t.fast_bytes};"
                   f"result_bytes_per_node={t.result_bytes_per_node};"
                   f"copies_per_node={copies_per_node(r)}")
        rows.append(f"{r.case.csv_name},{r.timing.median_us:.1f},{derived}")
    return rows


def to_report(suite: SuiteResult, *, quick: bool, reps: int,
              families: Sequence[str], elems: Sequence[int],
              dtypes: Sequence[str] = ("float32",)) -> dict:
    import jax
    matrix = sorted({r.case.topology for r in suite.cases})
    n_checks = sum(len(r.checks) for r in suite.cases) + \
        len(suite.cross_checks)
    return {
        "schema": SCHEMA_VERSION,
        "generated_by": "python -m repro.bench",
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "sweep": {"quick": quick, "reps": reps,
                  "families": list(families), "elems": list(elems),
                  "dtypes": list(dtypes)},
        "matrix": matrix,
        "cases": [case_record(r) for r in suite.cases],
        "cross_checks": [ch.to_dict() for ch in suite.cross_checks],
        "validation": {
            "ok": all(ch.ok for r in suite.cases for ch in r.checks)
                  and all(ch.ok for ch in suite.cross_checks),
            "num_checks": n_checks,
            "invariants": {
                "C1": "naive/shared resident-result bytes per node ratio "
                      "== ranks_per_node (measured from output shards)",
                "C2": "shared allgather moves zero intra-node copy bytes",
                "bridge": "shared-scheme bridge wire bytes == plans.py "
                          "slow_bytes (exact, ring model)",
            },
        },
    }


def write_report(report: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=False)
        f.write("\n")
