"""The expert layer's per-layer readers (``model.moe_ms``,
``model.moe_experts_ms``, ``model.moe_experts_roofline_share``,
``comm.alltoall_ms``, ``comm.alltoall_exposed_ms``) on a hand-made compiled
module and trace with known answers, and the expert products' FLOP and
byte count (``benchmark/experts.py``) at the Qwen3 cell's shapes."""

import json

import pytest

from benchharness import ROOT, load

S = "jit(step)/shard_map"

# the router's product; the experts' forward gather and, named by the
# compiler alone, a grouped product that feeds their backward; a
# synchronous bridge all-to-all of the dispatch and an asynchronous on-node
# one of the combine; the experts' backward; an attention product and an
# on-node all-gather, which are neither
HLO = f"""HloModule jit_step, is_scheduled=true

ENTRY %main.1 (z: f32[8]) -> f32[8] {{
  %z = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%z), kind=kOutput, calls=%c, metadata={{op_name="{S}/jvp()/while/body/moe/router/dot_general"}}
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%c, metadata={{op_name="{S}/jvp()/while/body/moe/experts/gather"}}
  %fusion.3 = f32[8]{{0}} fusion(%fusion.2), kind=kOutput, calls=%c, metadata={{op_name="ragged-dot-none"}}
  %all-to-all.4 = f32[8]{{0}} all-to-all(%fusion.2), replica_groups={{{{0,2}},{{1,3}}}}, metadata={{op_name="{S}/jvp()/while/body/moe/dispatch/comm.all_to_all[pod]/all_to_all"}}
  %all-to-all-start.5 = f32[8]{{0}} all-to-all-start(%all-to-all.4), replica_groups={{{{0,1}},{{2,3}}}}, metadata={{op_name="{S}/jvp()/while/body/moe/combine/comm.all_to_all[data]/all_to_all"}}
  %all-to-all-done.5 = f32[8]{{0}} all-to-all-done(%all-to-all-start.5), metadata={{op_name="{S}/jvp()/while/body/moe/combine/comm.all_to_all[data]/all_to_all"}}
  %fusion.7 = f32[8]{{0}} fusion(%all-to-all-done.5, %fusion.3), kind=kOutput, calls=%c, metadata={{op_name="{S}/transpose(jvp())/while/body/checkpoint/moe/experts/dot_general"}}
  %fusion.8 = f32[8]{{0}} fusion(%fusion.7), kind=kOutput, calls=%c, metadata={{op_name="{S}/jvp()/while/body/attn/dot_general"}}
  ROOT %all-gather.9 = f32[8]{{0}} all-gather(%fusion.8), replica_groups={{{{0,1}},{{2,3}}}}, dimensions={{0}}, metadata={{op_name="{S}/jvp()/while/body/attn/comm.all_gather[data]/all_gather"}}
}}
"""

# the same step with no expert layer (the program before it)
DENSE = "\n".join(line for line in HLO.splitlines()
                  if "moe/" not in line and "ragged" not in line)


def hand_made():
    """Window [0, 200] on one device: router 10 ns, experts' gather 30,
    the grouped product 20, the bridge all-to-all 20 (nothing computes
    beside it), the on-node one from 80 to 125 with the experts' backward
    (30) running from 85 to 115, attention 20, the all-gather 20."""
    ops = [
        ["fusion.1", "fusion", "f32[8]", 0, 10],
        ["fusion.2", "fusion", "f32[8]", 10, 40],
        ["fusion.3", "fusion", "f32[8]", 40, 60],
        ["all-to-all.4", "all-to-all", "f32[8]", 60, 80],
        ["all-to-all-start.5", "all-to-all-start", "f32[8]", 80, 85],
        ["fusion.7", "fusion", "f32[8]", 85, 115],
        ["all-to-all-done.5", "all-to-all-done", "f32[8]", 120, 125],
        ["fusion.8", "fusion", "f32[8]", 130, 150],
        ["all-gather.9", "all-gather", "f32[8]", 150, 170],
    ]
    return {"devices": {"/device:TPU:0": {"ops": ops, "async": []}},
            "host": [["bench.window", 0, 200]], "fused": {}}


def dense_trace():
    t = hand_made()
    dev = t["devices"]["/device:TPU:0"]
    dev["ops"] = [o for o in dev["ops"]
                  if o[0] in ("fusion.8", "all-gather.9")]
    return t


STEPS = 5
PEAK = {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def qwen3_cell() -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}["qwen3moe-train-4k-2x2"]
    config = json.loads((ROOT / "benchmark" / "configs" /
                         "qwen3-30b-a3b-ep2x2.json").read_text())
    traffic = json.loads((ROOT / "benchmark" / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    return {"config": config, "traffic": traffic, "chips": cell["chips"]}


class _View:
    def __init__(self, hlo, trace):
        self.raw = {"hlo_text": hlo, "steps": STEPS}
        self.trace = trace
        self.cell = qwen3_cell()
        self.peak = PEAK


@pytest.fixture
def readers(monkeypatch):
    """The readers over a stand-in for the traced run's profile:
    ``trace.extract`` gives ``made[0]``.  They import ``benchmark.scopes``
    and ``benchmark.experts``, as under ``run.py``."""
    import benchmark.experts as ex
    import benchmark.scopes as sc
    made = [hand_made()]
    tr = sc._trace_module()
    monkeypatch.setattr(tr, "latest_xplane", lambda d: "profile.xplane.pb")
    monkeypatch.setattr(tr, "extract", lambda path, hlo: made[0])
    monkeypatch.setattr(ex, "extract_one",
                        lambda path, plane, hlo: made[0])
    monkeypatch.setattr(sc.measure, "cache", None, raising=False)
    monkeypatch.setattr(ex.alltoall, "cache", None, raising=False)
    names = ("model.moe_ms", "model.moe_experts_ms",
             "model.moe_experts_roofline_share", "comm.alltoall_ms",
             "comm.alltoall_exposed_ms")
    mods = {n: load(f"benchmark/metrics/{n}.py") for n in names}
    return mods, made


def _view(hlo=HLO):
    return _View(hlo, {"busiest": "/device:TPU:0"})


def test_moe_layer_time(readers):
    """router 10 + gather 30 + grouped product 20 (named by its
    neighbour) + backward 30; the all-to-alls are collectives."""
    mods, _ = readers
    assert mods["model.moe_ms"].read(_view()) == pytest.approx(90 / 1e6 /
                                                               STEPS)


def test_moe_experts_time(readers):
    mods, _ = readers
    assert mods["model.moe_experts_ms"].read(_view()) == pytest.approx(
        80 / 1e6 / STEPS)


def test_roofline_share_of_the_experts(readers):
    """The least time at the cell's shapes over the 80 ns per 5 steps."""
    mods, _ = readers
    ex = load("benchmark/experts.py")
    least = ex.roofline_ms(qwen3_cell(), PEAK)
    got = mods["model.moe_experts_roofline_share"].read(_view())
    assert got == pytest.approx(100 * least / (80 / 1e6 / STEPS))


def test_alltoall_in_flight_and_exposed(readers):
    """In flight: [60, 80] and [80, 125], 65 ns; exposed: the 20 of the
    synchronous one, and [80, 85] and [115, 125] of the other, 35 ns."""
    mods, _ = readers
    assert mods["comm.alltoall_ms"].read(_view()) == pytest.approx(
        65 / 1e6 / STEPS)
    assert mods["comm.alltoall_exposed_ms"].read(_view()) == pytest.approx(
        35 / 1e6 / STEPS)


def test_alltoall_names_in_one_pass():
    """The op_names that name an all-to-all's scope, and no other."""
    ex = load("benchmark/experts.py")
    got = ex.alltoall_names(HLO)
    assert sorted(got) == ["all-to-all-done.5", "all-to-all-start.5",
                           "all-to-all.4"]
    assert got["all-to-all.4"].endswith("comm.all_to_all[pod]/all_to_all")


def test_one_device_read_as_the_whole_extraction_reads_it(tmp_path):
    """A profile recorded here: the host's benchmark spans, as
    ``trace.extract`` reads them, and the named device's ops, none on a
    host without one."""
    import jax
    import jax.numpy as jnp
    ex = load("benchmark/experts.py")
    tr = load("benchmark/trace.py")
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            jnp.ones(8).block_until_ready()
    path = tr.latest_xplane(str(tmp_path))
    got = ex.extract_one(path, "/device:TPU:0", "")
    whole = tr.extract(path, "")
    assert got["host"] == whole["host"]
    assert [h[0] for h in got["host"]] == ["bench.window"]
    assert got["devices"] == {} and got["fused"] == whole["fused"]


def test_nothing_to_read_without_an_expert_layer(readers):
    """A program with no expert layer and no all-to-all (the parent of
    this layer) reads nothing, and raises nothing."""
    mods, made = readers
    made[0] = dense_trace()
    for mod in mods.values():
        assert mod.read(_view(DENSE)) is None


def test_nothing_to_read_without_a_trace(readers):
    mods, _ = readers
    for mod in mods.values():
        assert mod.read(_View(HLO, None)) is None


def test_expert_work_at_the_cell_shapes():
    """4 layers x 3 passes (forward, and a backward of twice its work;
    no recomputation) of 16384 x 8 / 4 = 32768 routed rows through 3
    products of 2048 x 768 (2 FLOPs a multiply-add); bytes: 32 experts'
    f32 weights and the rows in and out."""
    ex = load("benchmark/experts.py")
    flops, bytes_ = ex.expert_work(qwen3_cell())
    assert flops == 3_710_851_743_744
    assert bytes_ == 13_690_208_256
    assert ex.roofline_ms(qwen3_cell(), PEAK) == pytest.approx(
        1e3 * 3_710_851_743_744 / 197e12)


def test_no_expert_work_in_a_dense_cell():
    ex = load("benchmark/experts.py")
    config = json.loads((ROOT / "benchmark" / "configs" /
                         "mistral-nemo-12b-hier2x2.json").read_text())
    assert ex.expert_work({"config": config, "traffic": {}, "chips": 4}) \
        is None
