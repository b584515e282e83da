"""Named scopes in the compiled train step.

The hier train step of a small dense decoder, compiled for four host
devices on a ``{"pod": 2, "data": 2, "model": 1}`` mesh, and its module read
the way the benchmark reads a chip's (``benchmark/scopes.py``): every
collective carries one ``comm.<primitive>[<axes>]`` scope whose axes agree
with the tier its replica groups span, every matrix product one of the
model's layer scopes, and AdamW's arithmetic the ``optimizer`` scope; the
ops the compiler makes without a layer in their own name take the layer
that ``scopes.instructions`` lends them.
"""

import collections
import importlib.util
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.core.topology import MeshTopology
from repro.runtime.steps import make_train_step
from repro.substrate.compat import make_mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]
MESH = {"pod": 2, "data": 2, "model": 1}


def _load(rel):
    spec = importlib.util.spec_from_file_location(
        "scopestest_" + pathlib.Path(rel).stem, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


scopes = _load("benchmark/scopes.py")
trace = scopes._trace_module()


@pytest.fixture(scope="module")
def compiled():
    """``(module text, number of parameter leaves)`` of the hier step."""
    c = json.loads((ROOT / "tests" / "benchmark" /
                    "tiny_dense.json").read_text())
    cfg = ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], act="swiglu",
        norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"])
    mesh = make_mesh(tuple(MESH.values()), tuple(MESH),
                     devices=jax.devices()[:4])
    bundle = make_train_step(cfg, MeshTopology(dict(MESH)), mesh,
                             mode="hier", compute_dtype=jnp.float32)
    state = bundle.init_state()
    tokens = jnp.zeros((8, 33), jnp.int32)
    text = jax.jit(bundle.fn).lower(state, {"tokens": tokens}) \
        .compile().as_text()
    return text, len(jax.tree.leaves(state["params"]))


def _instructions(text, timed=False):
    """``(name, opcode, op_name, line)`` of every instruction; with
    ``timed``, of those that run as ops of their own (not inside a
    computation that a fusion or a reduction calls)."""
    called = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    out, comp = [], None
    for line in text.splitlines():
        if not line[:1].isspace():
            comp = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", line)
            comp = comp.group(1) if comp else None
            continue
        line = line.strip().removeprefix("ROOT ")
        if " = " not in line or not line.startswith("%") or \
                (timed and comp in called):
            continue
        name, op, _ = trace.parse_hlo(line)
        m = re.search(r'op_name="([^"]*)"', line)
        out.append((name, op, m.group(1) if m else "", line))
    return out


def test_every_collective_has_one_comm_scope_of_its_tier(compiled):
    text, _ = compiled
    colls = [i for i in _instructions(text)
             if trace.COLLECTIVE.match(i[1])]
    assert colls
    tiers = set()
    for name, op, op_name, line in colls:
        found = scopes.comm_scopes(op_name)
        assert len(found) == 1, (name, op_name)
        tier = scopes.tier_of_axes(found[0][1])
        assert tier == scopes.tier_of_groups(
            scopes.parse_groups(line), MESH), (name, op_name, line)
        tiers.add(tier)
    assert tiers == {"node", "bridge"}


def test_every_matrix_product_has_a_layer(compiled):
    text, _ = compiled
    dots = [i for i in _instructions(text) if i[1] == "dot"]
    assert dots
    for name, _, op_name, _ in dots:
        assert scopes.layer_of(op_name) in ("embed", "attn", "mlp",
                                            "head"), (name, op_name)
    assert {scopes.layer_of(i[2]) for i in dots} == {"attn", "mlp", "head"}


def test_adamw_arithmetic_has_the_optimizer_scope(compiled):
    """Each leaf's update takes a square root (of v-hat), and so does the
    global gradient norm: all of them sit under ``optimizer``."""
    text, leaves = compiled
    roots = [i for i in _instructions(text) if i[1] == "sqrt"]
    assert len(roots) >= leaves
    for name, _, op_name, _ in roots:
        assert scopes.layer_of(op_name) == "optimizer", (name, op_name)


def test_unnamed_ops_take_the_layer_of_their_body_or_a_neighbour(compiled):
    """The ops the compiler makes without a layer in their own op_name,
    and the layer ``scopes.instructions`` lends each: from the fusion's
    body, or from its nearest named neighbour in the data flow.  The
    layer scan's slices of the stacked weights go to the layer they feed,
    the stacking of its gradients to the layer whose gradient it is, the
    reductions the compiler wraps without a name (norms, the loss's
    division, the global gradient norm) to their neighbours, and copies
    of the embedding table and of the scan's values to the layer that
    uses them.  Loop counters and constant fills stay unscoped."""
    text, _ = compiled
    names, _, borrowed = scopes.instructions(text, MESH)
    skip = {"parameter", "get-tuple-element", "tuple", "constant",
            "bitcast"}
    lent, bare = collections.defaultdict(set), []
    for name, op, op_name, line in _instructions(text, timed=True):
        if scopes.layer_of(op_name):
            assert name not in borrowed, (name, op_name)
            continue
        if op in skip or op in trace.CONTROL or trace.COLLECTIVE.match(op):
            continue
        layer = scopes.layer_of(names[name])
        if layer is None:
            bare.append(line)
            continue
        lent[re.sub(r"\.\d+$", "", name)].add((borrowed[name], layer))
    both = {"attn", "mlp"}
    assert dict(lent) == {
        "dynamic-slice_bitcast_fusion": {("neighbour", x) for x in both},
        "copy_bitcast_fusion": {("neighbour", x) for x in both},
        "bitcast_dynamic-update-slice_fusion": {("body", x) for x in both},
        "transpose_copy_fusion": {("body", "attn")},
        "wrapped_reduce-window": {("neighbour", x) for x in
                                  ("attn", "mlp", "head", "optimizer")},
        "wrapped_divide": {("neighbour", "head")},
        "copy": {("neighbour", x) for x in ("attn", "mlp", "embed")},
    }
    assert bare
    for line in bare:
        assert re.search(r"= (s32|pred)\[\]|broadcast|dynamic_update_slice",
                         line), line
