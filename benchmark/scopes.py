"""Device time per layer of the program and per tier of its collectives,
read from the program's named scopes.

The program names its device work with ``jax.named_scope``: ``embed``,
``attn``, ``mlp`` (or ``moe`` for an expert layer) and ``head`` in the
models, ``optimizer`` in the train step, and ``comm.<primitive>[<axes>]``
on every collective
(``repro.comm.primitives.scoped``; ``<axes>`` are the mesh axes it spans).
The compiled module keeps each scope as a component of an instruction's
``metadata={op_name="..."}``, under the wrappers of autodiff and remat
where those apply (``transpose(jvp(mlp))/...``,
``checkpoint/rematted_computation/mlp/...``), and a module may join
several names with ``;``.

Two steps, as in ``benchmark/trace.py``, so that the second can be checked
on a small trace recorded from a chip:

1. :func:`instructions` reads the compiled module: per instruction its
   op_name, followed by op_names of the instructions of the computations
   it calls (so that a fusion named after its matrix product also names
   the gather it carries, and a fusion the compiler left without a name
   takes one of its body) or, for an op the compiler made without a name
   (a layout copy), by that of its nearest named neighbour in the data
   flow; and, for a collective that no ``comm`` scope names (the compiler
   adds a few), the tier its replica groups span.
2. :func:`reduce` splits the busiest device's ops in the traced window:

   * **layer time**: each op that computes (as ``trace.device_summary``
     counts it: not control flow, and not a collective unless it also
     multiplies matrices) goes to the innermost of :data:`LAYERS` in its
     op_name, or to ``unscoped``, and to the first name of its op_name
     that carries a layer, which :func:`scope_ms` reads a part of a
     block from (``moe/dispatch``); each layer's time is also split into
     ``forward``, ``remat`` (under ``rematted_computation``) and
     ``backward`` (under ``transpose(...)``), and the part its ops took
     from a body or a neighbour, not from their own op_name, is reported
     apart (``borrowed_ns``);
   * **tier time**: the intervals of ``trace.collective_intervals``, the
     ones ``comm.collective_ms`` counts, split by the tier of their
     ``comm`` scope: ``bridge`` where its axes include the pod axis, else
     ``node``; each tier is a union of intervals.

:func:`measure` does both for a traced run of ``benchmark/run.py``: it
extracts the newest ``.xplane.pb`` under ``.bench_trace/`` again, once per
process.  The per-layer readers ``benchmark/metrics/model.*``,
``train.optimizer_ms`` and ``comm.node_ms``/``comm.bridge_ms`` import this
module as ``benchmark.scopes`` (``run.py`` puts the repository's root on
``sys.path``), so they share the one result through ``sys.modules``.  A
program without the scopes reads nothing, and a layer or part of a block
that no op of the step carries reads nothing: the readers then return
``None``.
"""

from __future__ import annotations

import collections
import importlib.util
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
LAYERS = ("embed", "attn", "mlp", "moe", "head", "optimizer")
UNSCOPED = "unscoped"
TIERS = ("node", "bridge")
PHASES = ("forward", "remat", "backward")
POD_AXIS = "pod"

_LAYER = re.compile(r"(?:^|[/(])(" + "|".join(LAYERS) + r")(?=[/)]|$)")
_COMM = re.compile(r"comm\.(\w+)\[([^\]]*)\]")
_GROUPS = re.compile(
    r"(?:replica_groups|source_target_pairs)=(\{[{}\d,\s]*\})")


def _trace_module():
    """``benchmark/trace.py``, loaded once per process."""
    name = "bench_trace"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, ROOT / "benchmark" / "trace.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


# ---------------------------------------------------------------------------
# Names
# ---------------------------------------------------------------------------

def named(op_name: str) -> str | None:
    """The first name of ``op_name`` (names joined by ``;``) that carries
    one of :data:`LAYERS`."""
    return next((one for one in op_name.split(";") if _LAYER.search(one)),
                None)


def layer_of(op_name: str) -> str | None:
    """The innermost of :data:`LAYERS` in :func:`named`."""
    one = named(op_name)
    return _LAYER.findall(one)[-1] if one else None


def phase_of(op_name: str) -> str:
    """``remat``, ``backward`` or ``forward``, from the wrappers of
    :func:`named`: a recomputation runs inside the backward pass, under
    ``transpose(...)`` and ``rematted_computation``."""
    one = named(op_name) or ""
    if "rematted_computation" in one:
        return "remat"
    if "transpose(" in one:
        return "backward"
    return "forward"


def in_scope(name: str, path: str) -> bool:
    """Whether ``path`` (``moe/dispatch``) is a run of whole components of
    ``name`` once the wrappers of autodiff and remat are taken off
    (``transpose(jvp(moe))/dispatch`` reads ``moe/dispatch``)."""
    bare = re.sub(r"[\w.\-]*\(|\)", "", name)
    return re.search(r"(?:^|/)" + re.escape(path) + r"(?=/|$)",
                     bare) is not None


def comm_scopes(op_name: str) -> list[tuple[str, tuple[str, ...]]]:
    """Every ``comm.<primitive>[<axes>]`` of the first name that carries
    one, outermost first, as ``(primitive, axes)``."""
    for one in op_name.split(";"):
        found = _COMM.findall(one)
        if found:
            return [(p, tuple(a for a in axes.split(",") if a))
                    for p, axes in found]
    return []


def tier_of_axes(axes) -> str:
    return "bridge" if POD_AXIS in axes else "node"


def parse_groups(text: str) -> list[list[int]] | None:
    """Replica groups (or source-target pairs) of an instruction's text,
    ``{{0,1},{2,3}}``; ``None`` where it lists none."""
    m = _GROUPS.search(text)
    if not m:
        return None
    return [[int(x) for x in grp.split(",") if x.strip()]
            for grp in re.findall(r"\{([\d,\s]*)\}", m.group(1))]


def tier_of_groups(groups, mesh: dict) -> str | None:
    """``bridge`` where a group holds devices of two pods, else ``node``;
    device ``d`` sits at ``d``'s row-major place in ``mesh`` (the order of
    ``shard_map``'s device assignment)."""
    if not groups or POD_AXIS not in mesh:
        return None
    after = 1
    for axis in list(mesh)[list(mesh).index(POD_AXIS) + 1:]:
        after *= mesh[axis]
    pods = mesh[POD_AXIS]
    spans = any(len({(d // after) % pods for d in g}) > 1 for g in groups)
    return "bridge" if spans else "node"


def instructions(hlo_text: str, mesh: dict | None = None
                 ) -> tuple[dict, dict, dict]:
    """``(op_names, tiers, borrowed)`` of a compiled module.

    ``op_names`` maps every instruction to its op_name, followed, where
    it calls computations (``calls=``), by the op_name of the first
    collective in them that a ``comm`` scope names and by one op_name of
    the layer most of their instructions carry.  An instruction that still
    names no layer (the compiler makes some without a name: layout copies
    of the optimizer's state, casts hoisted out of a loop) is followed by
    the op_name of its nearest neighbour in the data flow that names one,
    through unnamed instructions: the ops that use its result first, then
    those that make its operands (:func:`_neighbour`).  ``tiers`` maps
    each collective, and each instruction calling one, that no ``comm``
    scope names to the tier its replica groups span over ``mesh`` (the
    configuration's axis sizes, in mesh order).  ``borrowed`` maps each
    instruction whose own op_name names no layer, but whose entry in
    ``op_names`` does, to where that layer came from: ``body`` (the
    computations it calls) or ``neighbour``."""
    trace = _trace_module()
    own: dict[str, str] = {}
    groups: dict[str, list] = {}
    body: dict[str, list[str]] = {}
    calls: dict[str, list[str]] = {}
    operands: dict[str, list[str]] = {}
    opcode: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", line)
            comp = m.group(1) if m and line.rstrip().endswith("{") else None
            if comp:
                body[comp] = []
            continue
        if comp is None or " = " not in line:
            continue
        text = line.strip().removeprefix("ROOT ")
        name, op, _ = trace.parse_hlo(text)
        m = re.search(r'op_name="([^"]*)"', text)
        own[name] = m.group(1) if m else ""
        body[comp].append(name)
        operands[name], opcode[name] = _operands(text, op), op
        called = re.findall(r"\bcalls=%([\w.\-]+)", text)
        if called:
            calls[name] = called
        if trace.COLLECTIVE.match(op):
            # a -done names no groups: it takes those of its -start
            groups[name] = parse_groups(text) or next(
                (groups.get(n) for n in operands[name][:1]), None)

    def below(name, seen=()):
        out = []
        for c in calls.get(name, ()):
            if c in seen:
                continue
            for n in body.get(c, ()):
                out.append(n)
                out += below(n, seen + (c,))
        return out

    op_names, tiers, coll = {}, {}, {}
    for name, op_name in own.items():
        names, inner = [op_name], below(name)
        coll[name] = [n for n in inner if n in groups]
        scoped = [own[n] for n in coll[name] if comm_scopes(own[n])]
        names += scoped[:1]
        by_layer = collections.Counter(
            layer_of(own[n]) for n in inner if layer_of(own[n]))
        if by_layer:
            most = by_layer.most_common(1)[0][0]
            names.append(next(own[n] for n in inner
                              if layer_of(own[n]) == most))
        op_names[name] = ";".join(x for x in names if x)
    users: dict[str, list[str]] = collections.defaultdict(list)
    for name, ins in operands.items():
        for n in ins:
            users[n].append(name)
    fused = {n for c in calls.values() for comp in c
             for n in body.get(comp, ())}
    near = {}
    for name in op_names:
        if name in groups or coll[name] or name in fused or \
                layer_of(op_names[name]):
            continue
        found = _neighbour(name, op_names, users, operands, opcode)
        if found:
            near[name] = op_names[found]
    for name, op_name in near.items():
        op_names[name] = ";".join(x for x in (op_names[name], op_name) if x)
    borrowed = {name: "neighbour" if name in near else "body"
                for name, op_name in op_names.items()
                if layer_of(op_name) and not layer_of(own[name])}
    for name in op_names:
        mine = [name] if name in groups else coll[name]
        if mine and mesh and not comm_scopes(op_names[name]):
            t = tier_of_groups(groups[mine[0]], mesh)
            if t:
                tiers[name] = t
    return op_names, tiers, borrowed


def _operands(text: str, op: str) -> list[str]:
    """The instructions an instruction's text names as its operands."""
    rest = text.split(" = ", 1)[1]
    start = rest.find(f"{op}(")
    if start < 0:
        return []
    depth, i = 0, start + len(op)
    for j in range(i, len(rest)):
        depth += rest[j] == "("
        depth -= rest[j] == ")"
        if depth == 0:
            return re.findall(r"%([\w.\-]+)", rest[i:j])
    return []


def _neighbour(name, op_names, users, operands, opcode, depth: int = 4):
    """The nearest instruction, within ``depth`` steps through unnamed
    ones, whose op_name names a layer: among those that use ``name``'s
    result, else among those that make its operands; at one distance the
    layer most of them carry wins.  The walk does not pass through control
    flow: a value that enters a loop is used in the loop's body, not by
    the loop's users."""
    control = _trace_module().CONTROL
    for links in (users, operands):
        frontier, seen = [name], {name}
        for _ in range(depth):
            step = [n for f in frontier for n in links.get(f, ())
                    if n not in seen and n in op_names]
            seen.update(step)
            named = [n for n in step if layer_of(op_names[n])]
            if named:
                most = collections.Counter(
                    layer_of(op_names[n]) for n in named).most_common(1)
                return next(n for n in named
                            if layer_of(op_names[n]) == most[0][0])
            frontier = [n for n in step if opcode.get(n) not in control]
    return None


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def tier(name: str, op_names: dict, tiers: dict) -> str | None:
    """The tier of a collective op: that of its innermost ``comm`` scope,
    else that of its replica groups, else ``None``."""
    scopes = comm_scopes(op_names.get(name, ""))
    if scopes:
        return tier_of_axes(scopes[-1][1])
    return tiers.get(name)


def reduce(trace: dict, op_names: dict, tiers: dict | None = None,
           borrowed: dict | None = None, *, busiest: str | None = None,
           top: int = 10) -> dict:
    """Layer and tier time (ns) of one device over the traced window.

    ``trace`` is what ``trace.extract`` gives, ``op_names``, ``tiers`` and
    ``borrowed`` what :func:`instructions` gives; ``busiest`` names the
    device (``trace.reduce``'s ``busiest`` where not given).  Returns
    ``layers_ns`` (:data:`LAYERS` and ``unscoped``), ``named_ns`` (the
    time of the ops under each :func:`named` of the module's op_names,
    0 for one no op in the window carries), ``phases_ns`` per
    layer, ``borrowed_ns`` (the part of each layer's time whose ops took
    their layer from a ``body`` or a ``neighbour``, not their own name),
    ``tiers_ns``, ``tier_union_ns`` (node and bridge together),
    ``collective_ns`` (all collective intervals, as ``trace.reduce``
    counts them), ``compute_ns``, ``coverage`` (the share of compute time
    in the layers), ``own_coverage`` (the same by the ops' own names
    alone), ``unscoped_ops`` (the longest, as ``[name type, ns]``),
    ``borrowed_ops`` (the longest, as ``[name type layer body|neighbour,
    ns]``) and ``scoped`` (whether the module names any layer, and any
    collective)."""
    tr = _trace_module()
    tiers, borrowed = tiers or {}, borrowed or {}
    lo, hi = tr.window_of(trace["host"])
    if busiest is None:
        busiest = tr.reduce(trace)["busiest"]
    dev = trace["devices"][busiest]
    fused = trace.get("fused") or {}
    layers = dict.fromkeys(LAYERS + (UNSCOPED,), 0.0)
    phases = {k: dict.fromkeys(PHASES, 0.0) for k in LAYERS}
    lent = {k: dict.fromkeys(LAYERS, 0.0) for k in ("body", "neighbour")}
    unscoped: dict[str, float] = collections.Counter()
    lent_ops: dict[str, float] = collections.Counter()
    by_name = dict.fromkeys(
        {named(v) for v in op_names.values()} - {None}, 0.0)
    for name, op, typ, s, e in dev["ops"]:
        d = min(e, hi) - max(s, lo)
        if d <= 0 or op in tr.CONTROL:
            continue
        if not fused.get(name) and tr.collective(name, op, fused):
            continue
        op_name = op_names.get(name, "")
        layer = layer_of(op_name)
        if layer is None:
            layers[UNSCOPED] += d
            unscoped[f"{name} {typ}"] += d
            continue
        layers[layer] += d
        by_name[named(op_name)] += d
        phases[layer][phase_of(op_name)] += d
        if name in borrowed:
            lent[borrowed[name]][layer] += d
            lent_ops[f"{name} {typ} {layer} {borrowed[name]}"] += d
    tier_by_name = {n: tier(n, op_names, tiers)
                    for n in {o[0] for o in dev["ops"] + dev["async"]}}
    split = {}
    for t in TIERS:
        mine = {k: [o for o in dev[k] if tier_by_name[o[0]] == t]
                for k in ("ops", "async")}
        split[t] = tr.merge(tr.collective_intervals(mine, fused), lo, hi)
    every = tr.merge(tr.collective_intervals(dev, fused), lo, hi)
    union = tr.merge(split["node"] + split["bridge"], lo, hi)
    compute = sum(layers.values())
    own = compute - layers[UNSCOPED] - sum(
        sum(v.values()) for v in lent.values())
    values = set(op_names.values())
    return {
        "window_ns": hi - lo,
        "device": busiest,
        "layers_ns": layers,
        "named_ns": by_name,
        "phases_ns": phases,
        "borrowed_ns": lent,
        "tiers_ns": {t: tr.length(v) for t, v in split.items()},
        "tier_union_ns": tr.length(union),
        "collective_ns": tr.length(every),
        "compute_ns": compute,
        "coverage": (compute - layers[UNSCOPED]) / compute if compute else 0,
        "own_coverage": own / compute if compute else 0,
        "unscoped_ops": [list(kv) for kv in unscoped.most_common(top)],
        "borrowed_ops": [list(kv) for kv in lent_ops.most_common(top)],
        "scoped": {"layers": any(layer_of(v) for v in values),
                   "comm": any(comm_scopes(v) for v in values)},
    }


def measure(view) -> dict | None:
    """:func:`reduce` of a traced run of ``benchmark/run.py``, once per
    process; ``None`` without a trace.  Writes the whole result to
    standard error as one ``scopes`` line."""
    if view.trace is None:
        return None
    key = id(view.raw)
    cached = getattr(measure, "cache", None)
    if cached and cached[0] == key:
        return cached[1]
    t0 = time.monotonic()
    tr = _trace_module()
    mesh = view.cell["config"].get("mesh", {})
    trace = tr.extract(tr.latest_xplane(str(TRACE_DIR)),
                       view.raw["hlo_text"])
    out = reduce(trace, *instructions(view.raw["hlo_text"], mesh),
                 busiest=view.trace["busiest"])
    out["steps"] = view.raw["steps"]
    out["parse_s"] = time.monotonic() - t0
    print("scopes " + json.dumps(
        {k: v for k, v in out.items() if k != "named_ns"}), file=sys.stderr)
    measure.cache = (key, out)
    return out


def layer_ms(view, layer: str) -> float | None:
    """Milliseconds per step of ``layer`` on the busiest device; ``None``
    where no op of the step carries it."""
    out = measure(view)
    if out is None or not any(layer_of(n) == layer
                              for n in out["named_ns"]):
        return None
    return out["layers_ns"][layer] / 1e6 / out["steps"]


def scope_ms(view, path: str) -> float | None:
    """Milliseconds per step on the busiest device of the ops whose
    :func:`named` holds ``path`` (:func:`in_scope`): a part of a block,
    such as ``moe/dispatch``; ``None`` where no op of the step carries
    it."""
    out = measure(view)
    if out is None:
        return None
    mine = [ns for n, ns in out["named_ns"].items() if in_scope(n, path)]
    if not mine:
        return None
    return sum(mine) / 1e6 / out["steps"]


def tier_ms(view, tier_name: str) -> float | None:
    """Milliseconds per step with a collective of ``tier_name`` in flight
    on the busiest device."""
    out = measure(view)
    if out is None or not out["scoped"]["comm"] or \
            out["collective_ns"] <= 0:
        return None
    return out["tiers_ns"][tier_name] / 1e6 / out["steps"]
