"""The program's expert layer against the plain reference
(``benchmark/references/moe_decoder.py``), at a small size on the CPU.

The tiny Qwen3-MoE configuration runs through the harness's ``Program`` (the
production ``make_train_step(mode="hier")``) on four devices as 1x4, 2x2
and 4x1 meshes, one expert per chip; the layer alone runs with two experts
per chip, where each chip's share, and routing forced onto one chip, are
held to the uncut reference layer.  Float32 on the CPU computes every
product in full precision on both sides, so what separates them is the
order of the sums over chips: rounding alone, under 1e-5 relative."""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchharness import HERE, cpu_devices, load

MESHES = {"1x4": {"data": 4, "model": 1},
          "2x2": {"pod": 2, "data": 2, "model": 1},
          "4x1": {"pod": 4, "data": 1, "model": 1}}
TRAFFIC = {"kind": "train", "seq_len": 64, "global_batch": 8,
           "zipf_a": 1.3, "motif_len": 8, "motif_prob": 0.5}
SEED = 2 ** 31 + 11
# float32 sums over four chips in another order than the reference's
ROUNDING = 1e-5


def tiny_moe(mesh: dict, **keys) -> dict:
    config = json.loads((HERE / "tiny_moe.json").read_text())
    config.update(keys, mesh=dict(mesh), reference="moe_decoder")
    return config


@pytest.mark.parametrize("mesh", list(MESHES))
def test_three_steps_match_the_reference(mesh):
    """Loss and gradient norm of each of three steps, each leaf's first
    gradient and each leaf's change over the three steps."""
    train = load("benchmark/drivers/train.py")
    config = tiny_moe(MESHES[mesh])
    devices = cpu_devices(4)
    prog = train.Program(config, TRAFFIC, devices)
    pool = prog.batches(SEED)
    _, readings = prog.first_steps(SEED, pool)
    ref = train.reference_readings(config, TRAFFIC, SEED, devices)
    gaps = train.compare(readings, ref)
    assert gaps["loss_gap"] < ROUNDING
    assert gaps["gnorm_gap"] < ROUNDING
    # a leaf's norm: float32 sums over the leaf's elements as well
    assert gaps["grad_gap"] < 10 * ROUNDING
    assert gaps["update_gap"] < 10 * ROUNDING


# ---------------------------------------------------------------------------
# The layer alone: each chip's share, and routing forced onto one chip
# ---------------------------------------------------------------------------

E8 = {"num_experts": 8}       # two experts per chip over four


def _layer(config: dict):
    """The program's expert layer of ``config`` over its mesh, jitted:
    ``(x, params) -> x + layer(x)``, x of (8, 64, d) split over the
    data-parallel axes."""
    from repro.core.topology import MeshTopology
    from repro.models.moe import moe_block
    from repro.models.transformer import build
    from repro.runtime.steps import make_ctx
    from repro.substrate.compat import make_mesh, shard_map

    train = load("benchmark/drivers/train.py")
    cfg = train.model_config(config, TRAFFIC["seq_len"])
    axes = config["mesh"]
    topo = MeshTopology(dict(axes), slow_axes=("pod",) if "pod" in axes
                        else ())
    mesh = make_mesh(tuple(axes.values()), tuple(axes),
                     devices=cpu_devices(4))
    ctx = make_ctx(topo, "hier", jnp.float32)
    model = build(cfg, ctx, data=topo.size("data"))
    defs = model.defs["units"]["b0"]["moe"]
    specs = {k: P(*s[1:]) for k, s in
             model.param_specs()["units"]["b0"]["moe"].items()}

    def body(x, p):
        return moe_block(x, p, defs, ctx, cfg)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=(P(ctx.dp_axes),
                                                        specs),
                             out_specs=P(ctx.dp_axes), check_vma=False))


def _weights(config: dict, key):
    """The reference's weights of one expert layer, and the program's."""
    ref = load("benchmark/references/moe_decoder.py")
    w = {n: v[0] for n, v in ref.init_params(config, key).items()
         if n in ("router", "w_gate", "w_up", "w_down")}
    prog = {"ln": jnp.zeros(config["hidden_size"]),
            "router": w["router"],
            "w_in": jnp.stack([w["w_gate"], w["w_up"]], axis=-2)[None],
            "w_out": w["w_down"][None]}
    return w, prog


def _reference_layer(config: dict, x, w, keep=None):
    """x + the uncut reference layer; ``keep`` (E,) masks the experts."""
    ref = load("benchmark/references/moe_decoder.py")
    B, T, d = x.shape
    h = ref._rms_norm(x, 0.0, config["rms_norm_eps"]).reshape(B * T, d)
    g = ref.gates(config, h, w["router"])
    if keep is not None:
        g = g * keep
    return x + ref.experts(config, h, g, w).reshape(B, T, d)


def test_chip_shares_add_up_to_the_uncut_layer():
    """Each chip's experts alone (the others' weights zero) give, through
    the exchanges, that chip's part of the reference layer; the parts sum
    to the whole layer, which the program gives with every chip's experts
    in place."""
    config = tiny_moe(MESHES["2x2"], **E8)
    layer = _layer(config)
    w, p = _weights(config, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (8, 64, 64))
    whole = _reference_layer(config, x, w)
    parts = []
    for chip in range(4):
        keep = (jnp.arange(8) // 2 == chip).astype(jnp.float32)
        mine = dict(p, w_in=p["w_in"] * keep[None, :, None, None, None],
                    w_out=p["w_out"] * keep[None, :, None, None])
        got = layer(x, mine) - x
        want = _reference_layer(config, x, w, keep) - x
        np.testing.assert_allclose(got, want, atol=ROUNDING)
        parts.append(got)
    np.testing.assert_allclose(sum(parts), whole - x, atol=ROUNDING)
    np.testing.assert_allclose(layer(x, p), whole, atol=ROUNDING)


def test_routing_forced_onto_one_chip_drops_no_row():
    """A router that sends every token to experts 0 and 1, both held by
    the first chip: that chip receives every token of every chip, the most
    its sorted buffer holds, and every row comes back through it."""
    config = tiny_moe(MESHES["2x2"], **E8)
    layer = _layer(config)
    w, p = _weights(config, jax.random.key(5))
    common = jax.random.normal(jax.random.key(6), (64,))
    x = 0.1 * jax.random.normal(jax.random.key(7), (8, 64, 64)) + common
    unit = common / jnp.linalg.norm(common)
    router = w["router"].at[:, 0].set(4 * unit).at[:, 1].set(3 * unit)
    w, p = dict(w, router=router), dict(p, router=router)
    ref = load("benchmark/references/moe_decoder.py")
    h = ref._rms_norm(x, 0.0, config["rms_norm_eps"]).reshape(-1, 64)
    chosen = ref.gates(config, h, router) > 0
    assert chosen[:, :2].all() and not chosen[:, 2:].any()
    got, want = layer(x, p), _reference_layer(config, x, w)
    np.testing.assert_allclose(got, want, atol=ROUNDING)
    # no row lost: every token took its experts' output
    assert float(jnp.min(jnp.abs(got - x).sum(-1))) > 0.0


# ---------------------------------------------------------------------------
# The train step: gradient reduction, exchanges, load counter
# ---------------------------------------------------------------------------

def test_expert_gradients_are_not_reduced_over_any_axis():
    """On 2x2 each chip alone holds its experts: their gradients are summed
    over no axis, the bridge included; the router, held once per node and
    whole on each tensor-parallel rank, is summed over the pods and the
    model axis."""
    from repro.core.topology import MeshTopology
    from repro.models.transformer import build
    from repro.runtime.steps import make_ctx

    train = load("benchmark/drivers/train.py")
    cfg = train.model_config(tiny_moe(MESHES["2x2"]), 64)
    topo = MeshTopology(dict(MESHES["2x2"]), slow_axes=("pod",))
    ctx = make_ctx(topo, "hier")
    defs = build(cfg, ctx, data=2).defs["units"]["b0"]["moe"]
    assert defs["w_in"].ep_axes == ("pod", "data")
    assert ctx.grad_reduce_axes(defs["w_in"]) == ()
    assert ctx.grad_reduce_axes(defs["w_out"]) == ()
    assert ctx.grad_reduce_axes(defs["router"]) == ("pod", "model")


@functools.cache
def _compiled_2x2() -> str:
    """The compiled module of the tiny configuration's 2x2 train step."""
    train = load("benchmark/drivers/train.py")
    tokens = load("benchmark/tokens.py")
    prog = train.Program(tiny_moe(MESHES["2x2"]), TRAFFIC, cpu_devices(4))
    state = prog.init(tokens.seed_words(SEED))
    return prog.step.lower(state, {"tokens": prog.batches(SEED)[0]}) \
        .compile().as_text()


def test_compiled_step_moves_tokens_not_experts():
    """The 2x2 step's compiled module gathers no expert leaf (an
    f32[E,64,2,32] or f32[E,32,64] block), and the layers' scan holds the
    two-stage all-to-alls (pod, then data) of the dispatch's two buffers
    and the combine's one in the forward pass, of the dispatch's two in
    the backward pass's recomputation (the combine's output is not needed
    there) and of the three transposes."""
    text = _compiled_2x2()
    gathers = [line for line in text.splitlines()
               if re.search(r"\ball-gather(-start)?\(", line)]
    assert gathers
    assert not any(re.search(r"\d+,64,2,32\]|\d+,32,64\]", line)
                   for line in gathers)
    exchanges = re.findall(r"\ball-to-all(-start)?\(", text)
    assert len(exchanges) == 2 * (3 + 2 + 3)


def test_every_expert_product_is_read_as_moe_experts():
    """Every matrix product of the 2x2 step's expert layer (forward,
    backward, the pieces under a conditional included) is one that
    ``benchmark/scopes.py`` reads under ``moe/experts``, or the router's
    under ``moe/router``: the readers of ``model.moe_experts_ms`` and of its
    roofline share see all of the experts' work."""
    scopes = load("benchmark/scopes.py")
    trace = scopes._trace_module()
    text = _compiled_2x2()
    op_names, _, _ = scopes.instructions(text, MESHES["2x2"])
    seen = {"moe/experts": 0, "moe/router": 0}
    for line in text.splitlines():
        if " = " not in line or not line[:1].isspace():
            continue
        name, op, _ = trace.parse_hlo(line.strip().removeprefix("ROOT "))
        named = scopes.named(op_names.get(name, "")) or ""
        if op != "dot" or scopes.layer_of(named) != "moe":
            continue
        part = [p for p in seen if scopes.in_scope(named, p)]
        assert part, named
        seen[part[0]] += 1
    assert seen["moe/experts"] > seen["moe/router"] > 0


@pytest.mark.parametrize("routing,expected", [("balanced", 1.0),
                                              ("same", 4 / 2)])
def test_load_counter(monkeypatch, routing, expected):
    """``moe_load_max``, the busiest expert's routed rows over the mean:
    1 where every expert gets as many rows, E / k where every token goes
    to the same k experts."""
    import repro.models.moe as moe

    def route(h, router_w, top_k):
        n = h.shape[0]
        if routing == "balanced":
            idx = (jnp.arange(n * top_k) % 4).reshape(n, top_k)
        else:
            idx = jnp.broadcast_to(jnp.arange(top_k), (n, top_k))
        return idx.astype(jnp.int32), jnp.full((n, top_k), 1.0 / top_k)

    monkeypatch.setattr(moe, "route", route)
    train = load("benchmark/drivers/train.py")
    tokens = load("benchmark/tokens.py")
    prog = train.Program(tiny_moe(MESHES["2x2"]), TRAFFIC, cpu_devices(4))
    state = prog.init(tokens.seed_words(SEED))
    _, met = prog.step(state, {"tokens": prog.batches(SEED)[0]})
    assert float(met["moe_load_max"]) == pytest.approx(expected)


def test_cluster_step_holds_the_experts_as_the_train_step_does():
    """The elastic runtime's step (``make_cluster_train_step`` over a
    2-pod x 2-chip ``VirtualCluster``) holds each chip's experts over both
    tiers, and gives the production step's loss and gradient norm from
    the same state and batch: its gradient norm counts every chip's
    expert shards once."""
    from jax.sharding import NamedSharding

    from repro.runtime.steps import make_cluster_train_step
    from repro.substrate.cluster import VirtualCluster

    train = load("benchmark/drivers/train.py")
    tokens = load("benchmark/tokens.py")
    config = tiny_moe(MESHES["2x2"])
    opt = config["optimizer"]
    prog = train.Program(config, TRAFFIC, cpu_devices(4))
    words = tokens.seed_words(SEED)
    state = jax.tree.map(np.asarray, prog.init(words))
    pool = prog.batches(SEED)
    batch = np.asarray(pool[0])
    cluster = make_cluster_train_step(
        train.model_config(config, TRAFFIC["seq_len"]),
        VirtualCluster(pods=2, chips=2), lr=opt["lr"],
        weight_decay=opt["weight_decay"], clip=opt["clip"],
        global_batch=TRAFFIC["global_batch"])
    held = cluster.model.defs["units"]["b0"]["moe"]
    assert held["w_in"].ep_axes == held["w_out"].ep_axes == ("pod", "data")

    def put(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(cluster.mesh, s)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))

    _, got = jax.jit(cluster.fn)(put(state, cluster.state_specs),
                                 put({"tokens": batch}, cluster.batch_spec))
    _, want = prog.step(prog.init(words), {"tokens": pool[0]})
    for k in ("loss", "gnorm"):
        assert float(got[k]) == pytest.approx(float(want[k]), rel=ROUNDING)
