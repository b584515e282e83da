"""Layer and tier time from the program's named scopes
(``benchmark/scopes.py``): on a hand-made compiled module and trace with
known answers, and on a small trace recorded from a 2x2 v5e run of
``nemo-train-4k-2x2`` (two steps, cut from a traced window) with the
op_names of the instructions in it."""

import gzip
import json

import pytest

from benchharness import HERE, load

RECORDED = HERE / "recorded_scopes_nemo_2x2.json.gz"
MESH = {"pod": 2, "data": 2, "model": 1}
S = "jit(step)/shard_map"

# An all-gather run as a pair of fusions around the matrix product that
# carries it (the start names nothing, the product is named after its
# dot), a backward op under ``transpose(jvp(attn))``, an embedding op
# inside a while loop, a bridge psum, a collective the compiler added
# without a name, a reduce-scatter whose ``comm`` scope sits under
# ``head``, an op whose op_name joins two names, two layout copies the
# compiler made without a name (one into the optimizer's update, one out
# of it) and an unnamed copy that neither feeds nor is fed by a layer.
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[4,2]) -> (bf16[4,2], bf16[4,4]) {{
  %param_0 = bf16[4,2]{{1,0}} parameter(0)
  %all-gather.1 = bf16[4,4]{{1,0}} all-gather(%param_0), channel_id=1, replica_groups={{{{0,1}},{{2,3}}}}, dimensions={{1}}, metadata={{op_name="{S}/transpose(jvp())/while/body/checkpoint/rematted_computation/mlp/comm.all_gather[data]/all_gather"}}
  ROOT %tuple.1 = (bf16[4,2]{{1,0}}, bf16[4,4]{{1,0}}) tuple(%param_0, %all-gather.1)
}}

%inner.2 (p: f32[4,4], q: f32[4,4]) -> f32[4,4] {{
  %p = f32[4,4]{{1,0}} parameter(0)
  %q = f32[4,4]{{1,0}} parameter(1)
  ROOT %convolution.2 = f32[4,4]{{1,0}} convolution(%p, %q), dim_labels=bf_io->bf, metadata={{op_name="{S}/transpose(jvp())/while/body/checkpoint/rematted_computation/mlp/dot_general"}}
}}

%async_collective_fusion.3 (a: bf16[4,2], b: f32[4,4]) -> (f32[4,4], bf16[4,4]) {{
  %a = bf16[4,2]{{1,0}} parameter(0)
  %b = f32[4,4]{{1,0}} parameter(1)
  %all-gather.3 = bf16[4,4]{{1,0}} all-gather(%a), channel_id=2, replica_groups={{{{0,1}},{{2,3}}}}, dimensions={{1}}, metadata={{op_name="{S}/transpose(jvp())/while/body/checkpoint/rematted_computation/mlp/comm.all_gather[data]/all_gather"}}
  %fusion.9 = f32[4,4]{{1,0}} fusion(%b, %b), kind=kOutput, calls=%inner.2
  ROOT %tuple.3 = (f32[4,4]{{1,0}}, bf16[4,4]{{1,0}}) tuple(%fusion.9, %all-gather.3)
}}

%fused_computation.4 (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  ROOT %exponential.4 = f32[8]{{0}} exponential(%x), metadata={{op_name="{S}/transpose(jvp(attn))/exp"}}
}}

%add (l: f32[], r: f32[]) -> f32[] {{
  %l = f32[] parameter(0)
  %r = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%l, %r), metadata={{op_name="{S}/comm.psum[pod]/add"}}
}}

%body.5 (t: f32[8]) -> f32[8] {{
  %t = f32[8]{{0}} parameter(0)
  ROOT %fusion.10 = f32[8]{{0}} fusion(%t), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="{S}/jvp(embed)/mul"}}
}}

ENTRY %main.6 (w: bf16[4,2], v: f32[4,4], z: f32[8]) -> (f32[8], f32[8]) {{
  %w = bf16[4,2]{{1,0}} parameter(0)
  %v = f32[4,4]{{1,0}} parameter(1)
  %z = f32[8]{{0}} parameter(2)
  %async-collective-start.1 = (bf16[4,2]{{1,0}}, bf16[4,4]{{1,0}}) fusion(%w), kind=kCustom, calls=%fused_computation.1
  %fusion.7 = (f32[4,4]{{1,0}}, bf16[4,4]{{1,0}}) fusion(%w, %v), kind=kOutput, calls=%async_collective_fusion.3, metadata={{op_name="{S}/transpose(jvp())/while/body/checkpoint/rematted_computation/mlp/dot_general"}}
  %async-collective-done.1 = bf16[4,4]{{1,0}} fusion(%async-collective-start.1), kind=kCustom, calls=%fused_computation.1, metadata={{op_name="{S}/transpose(jvp())/while/body/checkpoint/rematted_computation/mlp/comm.all_gather[data]/all_gather"}}
  %fusion.8 = f32[8]{{0}} fusion(%z), kind=kLoop, calls=%fused_computation.4
  %while.1 = f32[8]{{0}} while(%z), condition=%body.5, body=%body.5
  %psum.12 = f32[8]{{0}} all-reduce(%z), channel_id=3, replica_groups={{{{0,2}},{{1,3}}}}, use_global_device_ids=true, to_apply=%add, metadata={{op_name="{S}/comm.psum[pod]/psum"}}
  %copy.19 = f32[8]{{0}} copy(%z)
  %fusion.14 = f32[8]{{0}} fusion(%copy.19), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="{S}/optimizer/sub"}}
  %copy.20 = f32[8]{{0}} copy(%fusion.14)
  %all-reduce.13 = f32[8]{{0}} all-reduce(%z), channel_id=4, replica_groups={{{{0,2}},{{1,3}}}}, use_global_device_ids=true, to_apply=%add
  %reduce-scatter.15 = f32[4]{{0}} reduce-scatter(%z), channel_id=5, replica_groups={{{{0,1}},{{2,3}}}}, dimensions={{0}}, to_apply=%add, metadata={{op_name="{S}/transpose(jvp(head))/comm.all_gather[data]/reduce_scatter"}}
  %copy.16 = f32[8]{{0}} copy(%z)
  %fusion.17 = f32[8]{{0}} fusion(%z), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="{S}/jvp(head)/mul"}}
  %fusion.18 = f32[8]{{0}} fusion(%z), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="{S}/jvp()/while/body/mlp/mul;{S}/jvp()/while/body/attn/add"}}
  ROOT %tuple.21 = (f32[8]{{0}}, f32[8]{{0}}) tuple(%fusion.18, %copy.20)
}}
"""


def hand_made():
    """One busy device over a window [0, 200] and an idler one."""
    ops = [
        ["async-collective-start.1", "fusion", "tuple", 0, 5],
        ["fusion.7", "fusion", "tuple", 5, 40],
        ["async-collective-done.1", "fusion", "bf16[4,4]", 40, 45],
        ["fusion.8", "fusion", "f32[8]", 45, 60],
        ["while.1", "while", "f32[8]", 60, 100],
        ["fusion.10", "fusion", "f32[8]", 62, 90],
        ["psum.12", "all-reduce", "f32[8]", 100, 120],
        ["fusion.14", "fusion", "f32[8]", 120, 130],
        ["all-reduce.13", "all-reduce", "f32[8]", 130, 140],
        ["reduce-scatter.15", "reduce-scatter", "f32[4]", 140, 150],
        ["copy.16", "copy", "f32[8]", 150, 155],
        ["fusion.17", "fusion", "f32[8]", 155, 170],
        ["fusion.18", "fusion", "f32[8]", 170, 180],
        ["copy.19", "copy", "f32[8]", 180, 185],
        ["copy.20", "copy", "f32[8]", 185, 190],
        ["fusion.14", "fusion", "f32[8]", 195, 210],
    ]
    idle = [["fusion.14", "fusion", "f32[8]", 0, 10]]
    tr = load("benchmark/trace.py")
    return {"devices": {"/device:TPU:0": {"ops": ops, "async": []},
                        "/device:TPU:1": {"ops": idle, "async": []}},
            "host": [["bench.window", 0, 200], ["bench.block", 180, 200]],
            "fused": tr.hlo_collectives(HLO)}


def test_names_through_wrappers():
    sc = load("benchmark/scopes.py")
    assert sc.layer_of(f"{S}/transpose(jvp(mlp))/mul") == "mlp"
    assert sc.layer_of(f"{S}/transpose(jvp())/while/body/checkpoint/"
                       "rematted_computation/attn/dot_general") == "attn"
    assert sc.layer_of(f"{S}/jvp(head)/comm.psum[model]/psum") == "head"
    assert sc.layer_of(f"{S}/optimizer/comm.psum[data,model]/psum") == \
        "optimizer"
    assert sc.layer_of(f"{S}/div") is None
    assert sc.layer_of("") is None
    # a name of the program's own functions is not a scope
    assert sc.layer_of(f"{S}/jit(headroom)/mlp_ish/mul") is None
    assert sc.phase_of(f"{S}/transpose(jvp(attn))/exp") == "backward"
    assert sc.phase_of(f"{S}/transpose(jvp())/checkpoint/"
                       "rematted_computation/mlp/mul") == "remat"
    assert sc.phase_of(f"{S}/jvp(embed)/mul") == "forward"
    assert sc.comm_scopes(f"{S}/jvp(head)/comm.psum[pod,data]/psum") == \
        [("psum", ("pod", "data"))]
    assert sc.comm_scopes(f"{S}/attn/dot_general") == []
    assert sc.tier_of_axes(("pod", "data")) == "bridge"
    assert sc.tier_of_axes(("data", "model")) == "node"


def test_replica_groups():
    sc = load("benchmark/scopes.py")
    assert sc.parse_groups("replica_groups={{0,1},{2,3}}, x") == \
        [[0, 1], [2, 3]]
    assert sc.parse_groups("source_target_pairs={{0,1},{2,3}}") == \
        [[0, 1], [2, 3]]
    assert sc.parse_groups("dimensions={0}") is None
    assert sc.tier_of_groups([[0, 2], [1, 3]], MESH) == "bridge"
    assert sc.tier_of_groups([[0, 1], [2, 3]], MESH) == "node"
    assert sc.tier_of_groups([[0, 1, 2, 3]], MESH) == "bridge"
    assert sc.tier_of_groups([[0, 1]], {"data": 2}) is None


def test_instructions():
    sc = load("benchmark/scopes.py")
    names, tiers, borrowed = sc.instructions(HLO, MESH)
    # the start names nothing itself: it takes its gather's scope
    assert sc.comm_scopes(names["async-collective-start.1"]) == \
        [("all_gather", ("data",))]
    # the carrying product keeps its own layer and names the gather
    assert sc.layer_of(names["fusion.7"]) == "mlp"
    assert sc.comm_scopes(names["fusion.7"]) == [("all_gather", ("data",))]
    # a fusion without a name takes the layer of its body
    assert sc.layer_of(names["fusion.8"]) == "attn"
    assert sc.phase_of(names["fusion.8"]) == "backward"
    # a copy without a name takes that of the update it feeds, or else of
    # the op that makes its operand; one with no named neighbour stays bare
    assert sc.layer_of(names["copy.19"]) == "optimizer"
    assert sc.layer_of(names["copy.20"]) == "optimizer"
    assert sc.layer_of(names["copy.16"]) is None
    # only the unnamed collective goes by its replica groups
    assert tiers == {"all-reduce.13": "bridge"}
    # the layers that no op's own name gives, and where they came from
    ops = {o[0] for o in hand_made()["devices"]["/device:TPU:0"]["ops"]}
    assert {n: h for n, h in borrowed.items() if n in ops} == {
        "async-collective-start.1": "body", "fusion.8": "body",
        "copy.19": "neighbour", "copy.20": "neighbour"}
    assert sc.layer_of(names["async-collective-start.1"]) == "mlp"


def test_hand_made_reduction():
    sc = load("benchmark/scopes.py")
    r = sc.reduce(hand_made(), *sc.instructions(HLO, MESH))
    assert r["device"] == "/device:TPU:0"
    # fusion.14 counts 120-130 and 195-200 (clipped to the window), its
    # copies 180-190
    assert r["layers_ns"] == {"embed": 28, "attn": 15, "mlp": 35 + 10,
                              "moe": 0, "head": 15,
                              "optimizer": 10 + 5 + 10, "unscoped": 5}
    assert r["phases_ns"]["mlp"] == {"forward": 10, "remat": 35,
                                     "backward": 0}
    assert r["phases_ns"]["attn"] == {"forward": 0, "remat": 0,
                                      "backward": 15}
    assert r["compute_ns"] == 133
    assert r["coverage"] == pytest.approx(128 / 133)
    # the unnamed fusion's 15 ns came from its body, the copies' 10 from
    # the optimizer's update they feed and are fed by
    assert r["borrowed_ns"] == {
        "body": {"embed": 0, "attn": 15, "mlp": 0, "moe": 0, "head": 0,
                 "optimizer": 0},
        "neighbour": {"embed": 0, "attn": 0, "mlp": 0, "moe": 0, "head": 0,
                      "optimizer": 10}}
    assert r["own_coverage"] == pytest.approx(103 / 133)
    assert r["borrowed_ops"] == [["fusion.8 f32[8] attn body", 15],
                                 ["copy.19 f32[8] optimizer neighbour", 5],
                                 ["copy.20 f32[8] optimizer neighbour", 5]]
    assert r["unscoped_ops"] == [["copy.16 f32[8]", 5]]
    # node: the gather pair 0-45 (the carrying product inside it) and the
    # reduce-scatter 140-150; bridge: the psum and the unnamed all-reduce
    assert r["tiers_ns"] == {"node": 45 + 10, "bridge": 20 + 10}
    assert r["tier_union_ns"] == r["collective_ns"] == 85
    assert r["scoped"] == {"layers": True, "comm": True}
    tr = load("benchmark/trace.py")
    assert r["collective_ns"] == \
        tr.reduce(hand_made())["devices"]["/device:TPU:0"]["collective_ns"]


class _View:
    def __init__(self, raw, trace, mesh=MESH):
        self.raw, self.trace = raw, trace
        self.cell = {"config": {"mesh": mesh}}


@pytest.fixture
def readers(monkeypatch):
    """The seven readers over a stand-in for the traced run's profile:
    ``trace.extract`` gives the hand-made trace.  They import
    ``benchmark.scopes``, as under ``run.py``."""
    import benchmark.scopes as sc
    tr = sc._trace_module()
    monkeypatch.setattr(tr, "latest_xplane", lambda d: "profile.xplane.pb")
    monkeypatch.setattr(tr, "extract", lambda path, hlo: hand_made())
    monkeypatch.setattr(sc.measure, "cache", None, raising=False)
    names = ["model.embed_ms", "model.attn_ms", "model.mlp_ms",
             "model.head_ms", "train.optimizer_ms", "comm.node_ms",
             "comm.bridge_ms"]
    return {n: load(f"benchmark/metrics/{n}.py") for n in names}


def test_readers_per_step(readers, capsys):
    view = _View({"hlo_text": HLO, "steps": 5},
                 {"busiest": "/device:TPU:0"})
    got = {n: r.read(view) for n, r in readers.items()}
    assert got == pytest.approx({
        "model.embed_ms": 28e-6 / 5, "model.attn_ms": 15e-6 / 5,
        "model.mlp_ms": 45e-6 / 5, "model.head_ms": 15e-6 / 5,
        "train.optimizer_ms": 25e-6 / 5, "comm.node_ms": 55e-6 / 5,
        "comm.bridge_ms": 30e-6 / 5})
    line = [x for x in capsys.readouterr().err.splitlines()
            if x.startswith("scopes ")]
    assert len(line) == 1      # measured once for all seven readers
    assert json.loads(line[0][len("scopes "):])["coverage"] > 0.9


def test_readers_read_nothing_without_scopes(readers):
    """A program without the scopes (the module's metadata gone) and a run
    without a trace: every reader returns ``None``."""
    import re
    bare = re.sub(r", metadata=\{[^{}]*\}", "", HLO)
    view = _View({"hlo_text": bare, "steps": 5},
                 {"busiest": "/device:TPU:0"})
    assert {n: r.read(view) for n, r in readers.items()} == \
        dict.fromkeys(readers)
    untraced = _View({"hlo_text": "", "steps": 5}, None)
    for r in readers.values():
        assert r.read(untraced) is None


@pytest.fixture(scope="module")
def recorded():
    """Two steps of the 2x2 cell as ``trace.extract`` gave them, with the
    op_names, tiers and borrowed layers ``scopes.instructions`` read from
    that run's compiled module for the instructions in them."""
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_names_cover_the_trace(recorded):
    names = {o[0] for dev in recorded["devices"].values()
             for k in ("ops", "async") for o in dev[k]}
    assert names <= set(recorded["op_names"])
    assert set(recorded["borrowed"]) <= names
    assert recorded["mesh"] == MESH
    # what the compiler added without a scope is on-node
    assert recorded["tiers"] and set(recorded["tiers"].values()) == {"node"}


DENSE = ("embed", "attn", "mlp", "head", "optimizer")


def test_recorded_layers_and_tiers(recorded):
    """Every chip: the five layers of a dense model hold at least 97% of
    the compute time (at least 95% by the ops' own names, the rest unnamed
    slices and stacks of the layer scan's weights and gradients that take
    their neighbour's layer), the two tiers together are exactly the collective
    time that ``comm.collective_ms`` counts, the on-node stage outweighs
    the bridge, and the op names show the forward, the recomputation and
    the backward pass of attention and MLP, and the embedding's
    scatter-add as its backward."""
    sc = load("benchmark/scopes.py")
    tr = load("benchmark/trace.py")
    whole = tr.reduce(recorded)
    assert len(whole["devices"]) == 4
    for plane, dev in whole["devices"].items():
        r = sc.reduce(recorded, recorded["op_names"], recorded["tiers"],
                      recorded["borrowed"], busiest=plane)
        assert r["scoped"] == {"layers": True, "comm": True}
        assert all(r["layers_ns"][k] > 0 for k in DENSE)
        assert r["layers_ns"]["moe"] == 0
        assert r["coverage"] >= 0.97
        assert 0.95 <= r["own_coverage"] < r["coverage"]
        lent = r["borrowed_ns"]
        assert sum(lent["body"].values()) < 1e-3 * r["compute_ns"]
        assert max(lent["neighbour"], key=lent["neighbour"].get) == "mlp"
        assert r["collective_ns"] == pytest.approx(dev["collective_ns"])
        assert r["tier_union_ns"] == pytest.approx(r["collective_ns"],
                                                   rel=5e-3)
        assert r["tiers_ns"]["node"] > r["tiers_ns"]["bridge"] > 0
        for layer in ("attn", "mlp"):
            assert all(v > 0 for v in r["phases_ns"][layer].values())
        embed = r["phases_ns"]["embed"]
        assert embed["backward"] > 10 * embed["forward"]
        assert r["phases_ns"]["optimizer"]["forward"] == \
            r["layers_ns"]["optimizer"]


def test_recorded_without_scopes(recorded):
    """The same trace read without the op_names, as from a program without
    the scopes: no layer, no tier, all compute unscoped."""
    sc = load("benchmark/scopes.py")
    r = sc.reduce(recorded, {}, {})
    assert r["scoped"] == {"layers": False, "comm": False}
    assert r["layers_ns"]["unscoped"] == r["compute_ns"] > 0
    assert r["tiers_ns"] == {"node": 0, "bridge": 0}
    assert r["collective_ns"] > 0


def test_recorded_reduces_to_the_same_numbers(recorded):
    """The recorded 2x2 window reduces, on its first chip, to the layer and
    tier times it gave before the ``moe`` layer and :func:`scope_ms`
    existed: no op of a dense model is read differently."""
    sc = load("benchmark/scopes.py")
    r = sc.reduce(recorded, recorded["op_names"], recorded["tiers"],
                  recorded["borrowed"], busiest="/device:TPU:0")
    assert r["layers_ns"] == {
        "embed": 59645638.0, "attn": 300902537.0, "mlp": 191237800.0,
        "moe": 0.0, "head": 36487362.0, "optimizer": 33782634.0,
        "unscoped": 10845441.0}
    assert r["tiers_ns"] == pytest.approx(
        {"node": 373496564.0, "bridge": 69323679.88888931})
    assert r["phases_ns"]["attn"] == {
        "forward": 62884914.0, "remat": 97774896.0, "backward": 140242727.0}
    assert r["compute_ns"] == 632901412.0
    assert {sc.layer_of(n) for n in r["named_ns"]} == set(DENSE)
    # each layer's time is the sum of the names that carry it
    for layer in DENSE:
        assert sum(ns for n, ns in r["named_ns"].items()
                   if sc.layer_of(n) == layer) == \
            pytest.approx(r["layers_ns"][layer])


# An expert layer's parts under the scan and its remat, one under the
# wrappers autodiff puts round the outermost scope, and attention.
MOE_HLO = f"""HloModule jit_step, is_scheduled=true

ENTRY %main.1 (z: f32[8]) -> f32[8] {{
  %z = f32[8]{{0}} parameter(0)
  %multiply.1 = f32[8]{{0}} multiply(%z, %z), metadata={{op_name="{S}/jvp()/while/body/moe/experts/dot_general"}}
  %multiply.2 = f32[8]{{0}} multiply(%multiply.1, %z), metadata={{op_name="{S}/transpose(jvp())/while/body/checkpoint/rematted_computation/moe/experts/dot_general"}}
  %multiply.3 = f32[8]{{0}} multiply(%multiply.2, %z), metadata={{op_name="{S}/transpose(jvp(moe))/dispatch/gather"}}
  %multiply.4 = f32[8]{{0}} multiply(%multiply.3, %z), metadata={{op_name="{S}/jvp()/while/body/attn/dot_general"}}
  ROOT %multiply.5 = f32[8]{{0}} multiply(%multiply.4, %z), metadata={{op_name="{S}/jvp()/while/body/moe/router/dot_general"}}
}}
"""


def moe_trace():
    ops = [["multiply.1", "multiply", "f32[8]", 0, 10],
           ["multiply.2", "multiply", "f32[8]", 10, 30],
           ["multiply.3", "multiply", "f32[8]", 30, 34],
           ["multiply.4", "multiply", "f32[8]", 34, 50],
           ["multiply.1", "multiply", "f32[8]", 90, 110]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "async": []}},
            "host": [["bench.window", 0, 100]], "fused": {}}


def test_names_of_an_expert_layer():
    sc = load("benchmark/scopes.py")
    assert sc.layer_of(f"{S}/jvp()/while/body/moe/experts/dot") == "moe"
    assert sc.layer_of(f"{S}/transpose(jvp(moe))/dispatch/gather") == "moe"
    assert sc.in_scope(f"{S}/transpose(jvp(moe))/dispatch/gather",
                       "moe/dispatch")
    assert sc.in_scope(f"{S}/jvp()/while/body/moe/experts/dot",
                       "moe/experts")
    assert not sc.in_scope(f"{S}/jvp()/while/body/moe/experts_x/dot",
                           "moe/experts")
    assert not sc.in_scope(f"{S}/jvp()/while/body/moe/experts/dot",
                           "moe/dispatch")
    assert sc.named(f"{S}/div;{S}/jvp(moe)/mul") == f"{S}/jvp(moe)/mul"


def test_an_expert_layer_reads_by_layer_and_by_part(monkeypatch):
    """Ops under ``moe`` count to that layer and are read a part at a
    time with :func:`scope_ms`; a layer or a part no op of the step
    carries reads nothing (``mlp`` here), and so does its reader."""
    import benchmark.scopes as sc
    tr = sc._trace_module()
    monkeypatch.setattr(tr, "latest_xplane", lambda d: "profile.xplane.pb")
    monkeypatch.setattr(tr, "extract", lambda path, hlo: moe_trace())
    monkeypatch.setattr(sc.measure, "cache", None, raising=False)
    view = _View({"hlo_text": MOE_HLO, "steps": 2},
                 {"busiest": "/device:TPU:0"})
    # multiply.1 counts 0-10 and 90-100 (clipped to the window)
    assert sc.layer_ms(view, "moe") == pytest.approx((20 + 20 + 4) / 2e6)
    assert sc.layer_ms(view, "attn") == pytest.approx(16 / 2e6)
    assert sc.scope_ms(view, "moe/experts") == pytest.approx(40 / 2e6)
    assert sc.scope_ms(view, "moe/dispatch") == pytest.approx(4 / 2e6)
    # carried by the module, never run in the window
    assert sc.scope_ms(view, "moe/router") == 0.0
    assert sc.scope_ms(view, "moe/combine") is None
    assert sc.layer_ms(view, "mlp") is None
    assert sc.layer_ms(view, "embed") is None
    assert load("benchmark/metrics/model.mlp_ms.py").read(view) is None
    assert sc.measure(view)["phases_ns"]["moe"] == {
        "forward": 20, "remat": 20, "backward": 4}
