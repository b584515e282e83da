"""The reduction from a device trace to the benchmark's metrics: busy union,
idle share, collective and exposed collective time, and the breakdown, on
a hand-made trace with known answers and on a small trace recorded from a
2x2 v5e run of ``nemo-train-4k-2x2`` (two steps, cut from a traced
window)."""

import gzip
import json

import pytest

from benchharness import HERE, load

RECORDED = HERE / "recorded_trace_nemo_2x2.json.gz"


def hand_made():
    """One device over a window [0, 100]: compute 0-10 and 30-60, a
    while loop 60-80 around compute 62-70, an async all-gather in flight
    20-50 (start and done ops 20-21 and 49-50), a synchronous all-reduce
    85-90; host spans label the gaps."""
    ops = [
        ["fusion.1", "fusion", "f32[8]", 0, 10],
        ["all-gather-start.1", "all-gather-start", "tuple", 20, 21],
        ["fusion.2", "fusion", "f32[8]", 30, 60],
        ["all-gather-done.1", "all-gather-done", "f32[16]", 49, 50],
        ["while.1", "while", "tuple", 60, 80],
        ["fusion.3", "fusion", "f32[8]", 62, 70],
        ["all-reduce.1", "all-reduce", "f32[8]", 85, 90],
    ]
    async_ = [["all-gather-start.1", "all-gather-start", "tuple", 20, 50]]
    host = [["bench.window", 0, 100], ["bench.dispatch", 10, 18],
            ["bench.block", 80, 100]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "async": async_}},
            "host": host}


# The shape of a compiled TPU module where an all-gather runs as fusions:
# a start and a done that only move data, a matrix product between them
# that carries the gather forward, and a reduce-scatter made one fusion.
HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[4,2]) -> (bf16[4,2], bf16[4,4]) {
  %param_0 = bf16[4,2]{1,0} parameter(0)
  %all-gather.1 = bf16[4,4]{1,0} all-gather(%param_0), dimensions={1}
  ROOT %tuple.1 = (bf16[4,2]{1,0}, bf16[4,4]{1,0}) tuple(%param_0, %all-gather.1)
}

%inner.2 (p: f32[4,4], q: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  %q = f32[4,4]{1,0} parameter(1)
  ROOT %convolution.2 = f32[4,4]{1,0} convolution(%p, %q), dim_labels=bf_io->bf
}

%async_collective_fusion.3 (a: bf16[4,2], b: f32[4,4]) -> (f32[4,4], bf16[4,4]) {
  %a = bf16[4,2]{1,0} parameter(0)
  %b = f32[4,4]{1,0} parameter(1)
  %all-gather.3 = bf16[4,4]{1,0} all-gather(%a), dimensions={1}
  %fusion.9 = f32[4,4]{1,0} fusion(%b, %b), kind=kOutput, calls=%inner.2
  ROOT %tuple.3 = (f32[4,4]{1,0}, bf16[4,4]{1,0}) tuple(%fusion.9, %all-gather.3)
}

%fused_computation.4 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %exponential.4 = f32[8]{0} exponential(%x)
}

%all-reduce-scatter.5 (y: f32[8]) -> f32[4] {
  %y = f32[8]{0} parameter(0)
  %all-reduce.5 = f32[8]{0} all-reduce(%y), to_apply=%add
  ROOT %slice.5 = f32[4]{0} slice(%all-reduce.5), slice={[0:4]}
}

ENTRY %main.6 (w: bf16[4,2], v: f32[4,4], z: f32[8]) -> f32[4] {
  %w = bf16[4,2]{1,0} parameter(0)
  %v = f32[4,4]{1,0} parameter(1)
  %z = f32[8]{0} parameter(2)
  %async-collective-start.1 = (bf16[4,2]{1,0}, bf16[4,4]{1,0}) fusion(%w), kind=kCustom, calls=%fused_computation.1
  %fusion.7 = (f32[4,4]{1,0}, bf16[4,4]{1,0}) fusion(%w, %v), kind=kOutput, calls=%async_collective_fusion.3
  %async-collective-done.1 = bf16[4,4]{1,0} fusion(%async-collective-start.1), kind=kCustom, calls=%fused_computation.1
  %fusion.8 = f32[8]{0} fusion(%z), kind=kLoop, calls=%fused_computation.4
  ROOT %fusion.10 = f32[4]{0} fusion(%fusion.8), kind=kCustom, calls=%all-reduce-scatter.5
}
"""


def test_hlo_collectives():
    tr = load("benchmark/trace.py")
    assert tr.hlo_collectives(HLO) == {
        "async-collective-start.1": False, "async-collective-done.1": False,
        "fusion.7": True, "fusion.10": False}
    assert tr.hlo_collectives("") == {}


def test_fused_collectives_in_a_trace():
    """An all-gather run as fusions: in flight from the start's begin to
    the done's end, hidden where the fusion that carries it multiplies."""
    tr = load("benchmark/trace.py")
    ops = [
        ["async-collective-start.1", "fusion", "tuple", 0, 5],
        ["fusion.7", "fusion", "tuple", 5, 40],
        ["fusion.8", "fusion", "f32[8]", 40, 50],
        ["async-collective-done.1", "fusion", "bf16[4,4]", 50, 60],
        ["fusion.10", "fusion", "f32[4]", 70, 80],
    ]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "async": []}},
             "host": [["bench.window", 0, 100]],
             "fused": tr.hlo_collectives(HLO)}
    dev = tr.reduce(trace)["devices"]["/device:TPU:0"]
    assert dev["busy_ns"] == 60 + 10
    assert dev["collective_ns"] == 60 + 10
    # exposed: the start 0-5, the done 50-60 and the reduce-scatter 70-80
    assert dev["exposed_ns"] == 5 + 10 + 10
    trace["fused"] = {}
    dev = tr.reduce(trace)["devices"]["/device:TPU:0"]
    assert dev["collective_ns"] == 0


def test_interval_arithmetic():
    tr = load("benchmark/trace.py")
    m = tr.merge([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 15)
    assert m == [[1, 4], [5, 8], [9, 15]]
    assert tr.length(m) == 12
    assert tr.subtract([[0, 10], [20, 30]], [[2, 4], [8, 22]]) == \
        [[0, 2], [4, 8], [22, 30]]


def test_parse_hlo():
    tr = load("benchmark/trace.py")
    assert tr.parse_hlo("%fusion.447 = bf16[36,4096]{1,0:T(8,128)(2,1)} "
                        "fusion(f32[1] %p), kind=kLoop") == \
        ("fusion.447", "fusion", "bf16[36,4096]")
    assert tr.parse_hlo("%while.1 = (s32[], f32[2]{0}) while((s32[], "
                        "f32[2]{0}) %t), body=%b") == \
        ("while.1", "while", "tuple")
    assert tr.collective("all-gather-start.3", "all-gather-start") == \
        ("all-gather.3", "-start")
    assert tr.collective("all-gather-done.3", "all-gather-done") == \
        ("all-gather.3", "-done")
    assert tr.collective("reduce-scatter.2-start", "async-start") == \
        ("reduce-scatter.2", "-start")
    assert tr.collective("all-reduce.1", "all-reduce") == \
        ("all-reduce.1", None)
    assert tr.collective("copy-start.1", "copy-start") is None


def test_hand_made_trace():
    tr = load("benchmark/trace.py")
    r = tr.reduce(hand_made())
    dev = r["devices"]["/device:TPU:0"]
    assert r["window_ns"] == 100
    # busy: 0-10, 20-21, 30-60 (with 49-50 inside), 60-80, 85-90
    assert dev["busy_ns"] == 10 + 1 + 30 + 20 + 5
    # in flight: 20-50 (async) and 85-90
    assert dev["collective_ns"] == 30 + 5
    # not hidden by compute (the while does not count): 20-30, 85-90
    assert dev["exposed_ns"] == 10 + 5
    ops = dict(r["breakdown"]["device_ops"])
    assert "while.1 tuple" not in ops
    assert ops["fusion.2 f32[8]"] == pytest.approx(30e-9)
    gaps = r["breakdown"]["idle_gaps"]
    # idle: 10-20, 21-30, 80-85, 90-100, longest first
    assert gaps[0] == ["bench.dispatch", pytest.approx(10e-9)]
    assert [g[1] for g in gaps] == pytest.approx([10e-9, 10e-9, 9e-9, 5e-9])
    labels = {g[0] for g in gaps}
    assert labels == {"bench.dispatch", "no host span", "bench.block"}


def test_recorded_trace():
    """Two steps of the 2x2 cell: the weight gathers run as fusions, so
    that more than half of the collective time is found only through the
    compiled module's fusions (``fused``, recorded with the trace)."""
    tr = load("benchmark/trace.py")
    with gzip.open(RECORDED, "rt") as f:
        trace = json.load(f)
    assert trace["fused"]
    opcodes_only = tr.reduce(dict(trace, fused={}))
    r = tr.reduce(trace)
    for p, dev in r["devices"].items():
        assert dev["collective_ns"] > 2 * \
            opcodes_only["devices"][p]["collective_ns"]
    assert len(r["devices"]) == 4
    for dev in r["devices"].values():
        assert 0 < dev["busy_ns"] <= r["window_ns"]
        assert 0 < dev["collective_ns"] <= r["window_ns"]
        assert 0 <= dev["exposed_ns"] <= dev["collective_ns"]
    busiest = r["devices"][r["busiest"]]["busy_ns"]
    assert busiest == max(d["busy_ns"] for d in r["devices"].values())
    bd = r["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    times = [t for _, t in bd["device_ops"]]
    assert times == sorted(times, reverse=True)
    idle = r["window_ns"] - busiest
    assert sum(t for _, t in bd["idle_gaps"]) <= idle / 1e9 + 1e-12
