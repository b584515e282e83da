"""The harness's correctness check, driven end to end on the CPU at a small
size with the look for a chip skipped: a sound step passes the limits of
the benchmark's cells, and each fault a training cell can have, planted
in the step underneath the harness, makes ``correct`` come out false.

Faults: a step that returns its state unchanged; half of the batch left
out, the mean taken over the rest; the exchange between chips left out
(the gradient bridge across pods skipped, 2x2 only).  A training cell
produces no tokens, so the altered-token fault does not apply."""

import contextlib
import time

import pytest

from benchharness import cpu_devices, load, tiny_cell

LIMITS = ["sc2-train-4k-1chip", "nemo-train-4k-2x2"]


def unchanged(fn):
    def step(state, batch):
        return state, fn(state, batch)[1]
    return step


def half_batch(fn):
    def step(state, batch):
        toks = batch["tokens"]
        return fn(state, {"tokens": toks[: toks.shape[0] // 2]})
    return step


@contextlib.contextmanager
def no_bridge():
    from repro.models.parallel import ParallelCtx
    orig = ParallelCtx.reduce_grads
    ParallelCtx.reduce_grads = lambda self, grads, *a, **k: grads
    try:
        yield
    finally:
        ParallelCtx.reduce_grads = orig


def drive(chips, wrap_step=None):
    """One run under the first cell's limits; returns it and whether its
    numbers pass each cell's limits."""
    train = load("benchmark/drivers/train.py")
    run = load("benchmark/run.py")
    cell = tiny_cell(chips=chips, limits_of=LIMITS[0])
    out = train.run(cell, seed=2 ** 31 + 101, seconds=0.2,
                    devices=cpu_devices(chips), t_start=time.monotonic(),
                    compiles=run.Compiles(), wrap_step=wrap_step)
    passes = {}
    for name in LIMITS:
        limits = tiny_cell(limits_of=name)["limits"]
        passes[name] = all(c["value"] <= limits[k]
                           for k, c in out["checks"].items() if k in limits)
    return out, passes


@pytest.mark.parametrize("chips", [1, 4])
def test_sound_step_is_correct(chips):
    out, passes = drive(chips)
    assert out["correct"], out["checks"]
    assert all(passes.values()), out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["compiles"] == 0


@pytest.mark.parametrize("fault", [unchanged, half_batch])
@pytest.mark.parametrize("chips", [1, 4])
def test_planted_fault_is_not_correct(chips, fault):
    out, passes = drive(chips, wrap_step=fault)
    assert not out["correct"], out["checks"]
    assert not any(passes.values()), out["checks"]


def test_bridge_left_out_is_not_correct():
    with no_bridge():
        out, passes = drive(4)
    assert not out["correct"], out["checks"]
    assert not any(passes.values()), out["checks"]
