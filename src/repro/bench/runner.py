"""Calibrated microbenchmark timer.

Fixes two bugs of the timer it replaced:

* the warmup expression called ``fn(*xs)`` up to three times (once for the
  ``isinstance`` probe, once per conditional branch) — here warmup is exactly
  ONE call;
* only ``jax.tree.leaves(out)[0]`` was blocked on, so multi-output
  computations (tuples, pytrees) could still be in flight when the clock
  stopped — here every leaf of every timed output is blocked on.

It also reports a median with dispersion instead of a bare mean: fake host
CPU devices schedule noisily, and the mean of 30 reps is dominated by the
slowest outliers.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import time

import jax


def block_all(out):
    """Block until *every* array leaf of ``out`` is ready (not just the
    first — the seed-timer bug this module exists to fix)."""
    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return out


@dataclasses.dataclass(frozen=True)
class TimingResult:
    """Median-of-reps timing with dispersion, all in microseconds."""

    median_us: float
    mean_us: float
    min_us: float
    max_us: float
    iqr_us: float       # p75 - p25 over the reps: the dispersion estimate
    reps: int
    inner: int          # calls per timed rep (calibrated; 1 unless tiny)
    # tail percentiles over the reps (nearest-rank): what the serving
    # family's latency reporting and the regression gate's p99 pass read.
    # p50 duplicates median on purpose — consumers address percentiles
    # uniformly without special-casing the 50th.
    p50_us: float = 0.0
    p99_us: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _percentile(sorted_us: list, q: float) -> float:
    """Nearest-rank percentile of an ascending sample list."""
    import math as _math
    n = len(sorted_us)
    return sorted_us[min(n - 1, max(0, _math.ceil(q * n) - 1))]


def calibrate_inner(warm_s: float, min_rep_s: float,
                    max_inner: int = 64) -> int:
    """Inner-loop count so one timed rep lasts at least ``min_rep_s``,
    given a ``warm_s``-second calibration call (1 = no batching).  The one
    home of this formula — ``timeit`` and ``suites.run_suite`` both use
    it."""
    if min_rep_s <= 0.0 or warm_s >= min_rep_s:
        return 1
    return min(max_inner, max(1, math.ceil(min_rep_s / max(warm_s, 1e-9))))


def summarize(times_us, inner: int = 1) -> TimingResult:
    """Aggregate raw per-rep microsecond samples into a ``TimingResult``
    (used by ``suites.run_suite``'s interleaved round-robin timing, where
    the rep loop lives OUTSIDE the per-case timer so concurrent cases share
    one drift profile)."""
    times_us = list(times_us)
    if not times_us:
        raise ValueError("no samples")
    if len(times_us) >= 2:
        q1, _, q3 = statistics.quantiles(times_us, n=4)
        iqr = q3 - q1
    else:
        iqr = 0.0
    ordered = sorted(times_us)
    return TimingResult(
        median_us=statistics.median(times_us),
        mean_us=statistics.fmean(times_us),
        min_us=ordered[0], max_us=ordered[-1],
        iqr_us=iqr, reps=len(times_us), inner=inner,
        p50_us=_percentile(ordered, 0.50),
        p99_us=_percentile(ordered, 0.99))


def timed_call(fn, *args, inner: int = 1) -> float:
    """One timed rep (``inner`` back-to-back calls, every output leaf
    blocked) in microseconds-per-call."""
    t0 = time.perf_counter()
    out = fn(*args)
    for _ in range(inner - 1):
        out = fn(*args)
    block_all(out)
    return (time.perf_counter() - t0) / inner * 1e6


def timeit(fn, *args, reps: int = 30, min_rep_s: float = 0.0,
           max_inner: int = 64, warmup: bool = True) -> TimingResult:
    """Time ``fn(*args)``: one warmup call, then ``reps`` timed reps.

    Calibration: the warmup call is also timed; if it ran faster than
    ``min_rep_s``, each rep loops ``fn`` ``inner`` times (capped at
    ``max_inner``) so a rep is long enough for the clock.  Every rep blocks
    on all output leaves before the clock stops.

    ``warmup=False`` is for callers that already executed ``fn`` once
    (e.g. ``run_suite`` runs each compiled case once to inspect its output
    shards — THAT is the single warmup); calibration then uses the first
    timed rep, which stays in the measured set.
    """
    if reps < 1:
        raise ValueError(f"reps must be >= 1, got {reps}")
    inner = 1
    if warmup:
        t0 = time.perf_counter()
        block_all(fn(*args))             # the single warmup call
        warm_s = time.perf_counter() - t0
        inner = calibrate_inner(warm_s, min_rep_s, max_inner)
    times_us = []
    for i in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        for _ in range(inner - 1):
            out = fn(*args)
        block_all(out)
        dt = time.perf_counter() - t0
        times_us.append(dt / inner * 1e6)
        if not warmup and i == 0:
            inner = calibrate_inner(dt, min_rep_s, max_inner)
    return summarize(times_us, inner=inner)
