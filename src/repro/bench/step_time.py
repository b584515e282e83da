"""End-to-end ``step_time`` bench family: whole train steps, not medians of
one collective.

The single-collective families measure each schedule in isolation; what the
async prefetch engine actually buys — layer *k+1*'s FSDP window gather
overlapping layer *k*'s compute — only shows up in a full forward/backward
step.  This family times exactly that, over the model-zoo configs, through
the same machinery as every other family: its two schemes are registry
entries, its cases carry traffic expectations that ``repro.bench.validate``
cross-checks against the compiled HLO, its cells land in
``BENCH_collectives.json`` and fold into the ``scheme="auto"`` tuning table,
and the CI regression gate diffs it like any ``allgather`` cell.

* ``eager``    — issue-at-use baseline: the unit loop fully unrolled
  (``lax.scan(unroll=n_units)``), weight gathers issued inside each unit
  body at use time and re-issued by the remat bwd;
* ``prefetch`` — the same step with the ``prefetch`` opt: the unrolled
  ``ParamGroup`` walk (``models.parallel``) that issues the next unit's
  gathers as ``AsyncCollectiveHandle``s while the current unit computes;
* ``stepgraph`` — the same step with the ``stepgraph`` opt: the step's
  scalar stats and per-leaf gradient reductions recorded into one
  ``CollectiveGraph`` (``repro.comm.stepgraph``) and re-issued as the
  bucketed/deduped/reordered schedule — fewer, larger bridge messages,
  bit-identical outputs.

Both schemes unroll the unit loop, so the measured delta isolates the
prefetch engine (gather placement and issue order) — rolled-scan vs
unrolled is an orthogonal code-layout effect that would otherwise swamp
the comparison on small reduced configs.  Production training keeps its
rolled scan; this family measures the *schedule*, not the loop form.

A step's collective content is whatever the model traced — there is no
closed form in ``(pods, chips, elems)`` alone — so each scheme carries a
per-config **link inventory** recorded by the case builder from the step's
own jaxpr (``link_inventory``), priced with the very ring model
``analysis.roofline.parse_collectives`` applies to the compiled HLO.  The
jaxpr is what we asked for and the HLO is what XLA lowered, so the
``link/fast``/``link/slow`` checks pin real rewrites (a lost overlap, an
accidental re-gather, a wrong replica group), not a tautology.

Case sizing: ``elems`` is the model's global parameter element count —
deterministic per config, so quick (CI) and full sweeps land on the same
(family, topology, dtype, size) cells and stay comparable.
"""

from __future__ import annotations

import dataclasses
import math
from types import MappingProxyType
from typing import Optional

import jax
from jax.extend import core as jex_core
from jax.interpreters.partial_eval import dce_jaxpr

from repro.bench.suites import ELEM_BYTES, BenchCase, _swept
from repro.comm import registry
from repro.comm.registry import CollectiveScheme, register_scheme
from repro.configs import get_config
from repro.core.plans import CollectiveTraffic, collective_time_model

#: model-zoo configs timed by the family (reduced shapes: the bench measures
#: schedules, not model quality).  Both are plain dense, untied-embedding
#: entries on purpose: a tied unembed gathers the SAME leaf twice and XLA
#: CSE merges the two gathers, which a jaxpr-side count cannot see.
STEP_CONFIGS = ("starcoder2-7b", "mistral-nemo-12b")


# ---------------------------------------------------------------------------
# Jaxpr link inventory (the expected side of the HLO cross-check)
# ---------------------------------------------------------------------------

_AR_LIKE = ("psum", "pmax", "pmin")


def _names(axis_name) -> tuple[str, ...]:
    if axis_name is None:
        return ()
    if isinstance(axis_name, (tuple, list)):
        return tuple(a for a in axis_name if isinstance(a, str))
    return (axis_name,) if isinstance(axis_name, str) else ()


def _aval_bytes(v) -> int:
    aval = v.aval
    return math.prod(aval.shape) * aval.dtype.itemsize


def _inner_jaxprs(eqn):
    kinds = (jex_core.ClosedJaxpr, jex_core.Jaxpr)
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, kinds):
                yield v


def _scan_copies(eqn) -> int:
    """Static body copies a ``scan`` leaves in the lowered module text.

    ``unroll`` is a lowering-time knob invisible in the jaxpr structure:
    the body jaxpr stays one step, but lowering emits ``unroll`` copies
    inside the loop (all of them when fully unrolled, where the loop
    disappears entirely)."""
    length = eqn.params.get("length", 1) or 1
    unroll = eqn.params.get("unroll", 1)
    if unroll is True:
        return length
    return min(int(unroll) or 1, length)


@dataclasses.dataclass(frozen=True)
class LinkEntry:
    """One physical collective message in a traced step's lowering: the
    unit the inventory sums and the bucketing/dedup tests count."""

    kind: str                   # "ar" | "ag" | "rs" | "a2a" | "perm"
    names: tuple[str, ...]      # axis names the group spans
    tier: str                   # "fast" | "slow" (any pod axis -> slow)
    out_bytes: int              # result payload of the op
    link_bytes: float           # ring-model per-chip wire bytes, one copy
    copies: float               # static lowered copies (unrolled scans)
    group_size: int             # ranks per replica group


def _inner_roots(eqn, inner, root) -> dict:
    """Map ``inner``'s invars to the values they carry in from ``eqn``, so
    the same value keeps one identity across nested bodies.

    A call-like body (remat, pjit, custom rules) binds the eqn's operands
    positionally.  A fully unrolled ``scan`` lowers inline, so its consts
    keep their identity and each ``xs`` slice is the matching slice of the
    outer array; its carries change every step and bind nothing.  A loop
    that stays a loop is its own computation, where XLA's CSE cannot reach
    the outside, and binds nothing either."""
    if eqn.primitive.name == "scan":
        length = eqn.params.get("length", 1) or 1
        if _scan_copies(eqn) != length:
            return {}
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        out = {id(i): root(o) for i, o in zip(inner.invars[:nc],
                                              eqn.invars[:nc])}
        out.update({id(i): ("xs", root(o)) for i, o in
                    zip(inner.invars[nc + ncar:], eqn.invars[nc + ncar:])})
        return out
    if len(inner.invars) != len(eqn.invars):
        return {}
    return {id(i): root(o) for i, o in zip(inner.invars, eqn.invars)}


def _walk(jaxpr, sizes: dict, pod_names: set, entries: list,
          mult: float = 1.0, roots: Optional[dict] = None,
          fwd_seen: Optional[set] = None, in_remat: bool = False) -> None:
    # identical collective eqns over the same operand values are one HLO op
    # after XLA's CSE — count them once per body.  Values keep one identity
    # across nested bodies (``_inner_roots``), which matters for remat: the
    # compiled program keeps a backward recompute's collective only when no
    # identical collective ran outside every remat body (its forward twin,
    # e.g. the weight gather the recompute repeats), while recomputes in
    # two different remat bodies stay two ops.
    roots = {} if roots is None else roots
    fwd_seen = set() if fwd_seen is None else fwd_seen
    seen = set()

    def root(v):
        return roots.get(id(v), id(v))

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _AR_LIKE:
            names = _names(eqn.params.get("axes", ()))
            kind = "ar"
        elif prim == "all_gather":
            names = _names(eqn.params.get("axis_name"))
            kind = "ag"
        elif prim == "reduce_scatter":
            names = _names(eqn.params.get("axis_name"))
            kind = "rs"
        elif prim == "all_to_all":
            names = _names(eqn.params.get("axis_name"))
            kind = "a2a"
        elif prim == "ppermute":
            names = _names(eqn.params.get("axis_name"))
            kind = "perm"
        else:
            # loop/branch/remat/pjit bodies appear once in the lowered
            # module text, which is exactly how parse_collectives counts
            # them — recurse once per eqn; a partially/fully unrolled scan
            # body is the one exception (``unroll`` static copies)
            inner_mult = mult * _scan_copies(eqn) if prim == "scan" else mult
            for inner in _inner_jaxprs(eqn):
                _walk(inner, sizes, pod_names, entries, inner_mult,
                      _inner_roots(eqn, inner, root), fwd_seen,
                      in_remat or prim == "remat2")
            continue
        if not names:
            continue            # positional-axes only: no wire traffic
        groups = eqn.params.get("axis_index_groups")
        if groups is not None:
            n = len(groups[0])
        else:
            n = 1
            for a in names:
                n *= sizes.get(a, 1)
        if n <= 1:
            continue
        key = (prim, tuple(map(root, eqn.invars)),
               tuple(sorted((k, repr(v)) for k, v in eqn.params.items())))
        if key in seen or key in fwd_seen:
            continue
        seen.add(key)
        if not in_remat:
            fwd_seen.add(key)
        out_b = sum(_aval_bytes(v) for v in eqn.outvars)
        if kind == "ag":
            link = out_b * (n - 1) / n
        elif kind == "rs":
            link = out_b * (n - 1)
        elif kind == "ar":
            link = 2.0 * out_b * (n - 1) / n
        elif kind == "a2a":
            link = out_b * (n - 1) / n
        else:                   # ppermute -> collective-permute
            link = float(out_b)
        tier = "slow" if any(a in pod_names for a in names) else "fast"
        entries.append(LinkEntry(kind=kind, names=names, tier=tier,
                                 out_bytes=out_b, link_bytes=link,
                                 copies=mult, group_size=n))


def _traced_entries(fn, example_args, vc) -> list[LinkEntry]:
    closed = jax.make_jaxpr(fn)(*example_args)
    jaxpr, _ = dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    sizes = dict(zip(vc.axis_names, vc.axis_shapes))
    entries: list[LinkEntry] = []
    _walk(jaxpr, sizes, set(vc.slow_names), entries)
    return entries


def link_entries(fn, example_args, vc) -> list[LinkEntry]:
    """Per-message inventory of ``fn``'s lowering: one ``LinkEntry`` per
    physical collective (post-DCE, per-jaxpr CSE applied the way jit
    applies it, ``axis_index_groups``-aware).  This is how the step-graph
    tests verify bucketing/dedup did what they claim — counting entries
    counts messages, not bytes."""
    return _traced_entries(fn, example_args, vc)


def link_inventory(fn, example_args, vc) -> tuple[float, float]:
    """Expected per-chip (fast, slow) link bytes of ``fn``'s lowering.

    Traces ``fn`` (a mesh-level function, e.g. an ``smap``-wrapped body) to
    a jaxpr, DCEs it the way jit will, and prices every collective primitive
    with ``parse_collectives``' ring model: AG ``out*(n-1)/n``, RS
    ``out*(n-1)``, AR ``2*out*(n-1)/n``, A2A ``out*(n-1)/n``, permute
    ``out``.  Loop bodies count once (static module text); size-1 groups are
    skipped; a group naming a slow axis is charged to the bridge tier.
    Sums ``link_entries`` — the per-message detail the step-graph tests
    assert on.
    """
    fast = slow = 0.0
    for e in _traced_entries(fn, example_args, vc):
        if e.tier == "slow":
            slow += e.link_bytes * e.copies
        else:
            fast += e.link_bytes * e.copies
    return fast, slow


# ---------------------------------------------------------------------------
# The two step schemes
# ---------------------------------------------------------------------------

def _no_dispatch(*_a, **_k):
    raise NotImplementedError(
        "step_time schemes are whole-train-step bench entries; they have no "
        "Communicator dispatch body — build cases via "
        "repro.bench.step_time.step_time_cases")


class StepTimeScheme(CollectiveScheme):
    """Base of the ``step_time`` schemes: a registry entry whose expected
    lowering is a recorded per-config inventory instead of a closed form.

    ``step_time_cases`` records each built case's jaxpr inventory here;
    ``links()`` replays it for ``validate.expected_links``, ``traffic``/
    ``predicted_time`` express it in ``core.plans`` terms so the tuning
    table's modeled fallback ranks the schemes off-table too.
    """

    result_class = "replicated"
    FAMILY = "step_time"        # subclasses re-key (e.g. bench.serving)
    ops = MappingProxyType({"step_time": _no_dispatch})
    opts: tuple = ()            # ParallelCtx opts that select this schedule
    N_OUT = 3                   # loss, gnorm, checksum: replicated f32

    def __init__(self):
        # (pods, chips, fast_shape, elems) -> (fast, slow) per-chip bytes
        self._inventory: dict = {}

    def record(self, *, pods: int, chips: int, fast_shape, elems: int,
               fast: float, slow: float) -> None:
        self._inventory[(pods, chips, tuple(fast_shape), elems)] = \
            (fast, slow)

    def _lookup(self, pods: int, chips: int, elems: int
                ) -> Optional[tuple[float, float]]:
        for (p, c, _fs, e), v in self._inventory.items():
            if (p, c, e) == (pods, chips, elems):
                return v
        return None

    def links(self, family, *, pods, chips, fast_shape, elems, elem_bytes=4,
              opts=None, dtype="float32"):
        inv = self._inventory.get((pods, chips, tuple(fast_shape), elems))
        if inv is None:
            raise ValueError(
                f"{self.name!r} has no recorded link inventory for "
                f"{pods}x{chips} (fast {fast_shape}) at {elems} elems — "
                f"{self.FAMILY} expectations are recorded per case by the "
                "family's case builder, not closed forms")
        return inv

    def result_node(self, family, *, pods, chips, elems, elem_bytes=4):
        # replicated scalars: every rank holds each f32 output once
        return self.N_OUT * 4 * chips

    def traffic_for(self, *, pods: int, chips: int, fast_shape, elems: int
                    ) -> CollectiveTraffic:
        fast, slow = self.links(self.FAMILY, pods=pods, chips=chips,
                                fast_shape=fast_shape, elems=elems)
        R = pods * chips
        return CollectiveTraffic(
            slow_bytes=slow * R, fast_bytes=fast * R,
            result_bytes_per_node=self.result_node(
                self.FAMILY, pods=pods, chips=chips, elems=elems))

    def traffic(self, family, *, pods, chips, elems, elem_bytes=4,
                populations=None):
        if family != self.FAMILY:
            return super().traffic(family, pods=pods, chips=chips,
                                   elems=elems, elem_bytes=elem_bytes,
                                   populations=populations)
        inv = self._lookup(pods, chips, elems)
        if inv is None:
            raise ValueError(f"{self.name!r}: no recorded inventory for "
                             f"{pods}x{chips}/e{elems}")
        R = pods * chips
        return CollectiveTraffic(
            slow_bytes=inv[1] * R, fast_bytes=inv[0] * R,
            result_bytes_per_node=self.result_node(
                family, pods=pods, chips=chips, elems=elems))

    def predicted_time(self, family, *, pods, chips, elems, elem_bytes=4,
                       populations=None):
        if self._lookup(pods, chips, elems) is None:
            return None         # unrecorded config: cannot rank off-table
        tr = self.traffic(family, pods=pods, chips=chips, elems=elems)
        return collective_time_model(tr, num_nodes=pods,
                                     ranks_per_node=chips), {}


class StepEagerScheme(StepTimeScheme):
    """Issue-at-use baseline: the unit loop fully unrolled, weight gathers
    issued inside each unit body at use time (and re-issued by the remat
    bwd) — the prefetch schedule minus the prefetching."""

    name = "eager"
    opts = ()


class StepPrefetchScheme(StepTimeScheme):
    """The async-prefetch step: unrolled ``ParamGroup`` walk, unit *k+1*'s
    gathers in flight (``AsyncCollectiveHandle``) while unit *k* computes,
    double-buffered (in-flight budget 2)."""

    name = "prefetch"
    opts = ("prefetch",)


class StepStepgraphScheme(StepTimeScheme):
    """The step-graph-optimized step: scalar stats + per-leaf gradient
    reductions recorded into one ``CollectiveGraph`` and re-issued as the
    rewritten schedule (``repro.comm.stepgraph``) — small same-axes
    allreduces packed into flat buckets sized off the tuning table, issues
    front-loaded behind one shared ordering token.  Fewer, larger bridge
    messages; outputs bit-identical to ``eager``."""

    name = "stepgraph"
    opts = ("stepgraph",)


EAGER = register_scheme(StepEagerScheme())
PREFETCH = register_scheme(StepPrefetchScheme())
STEPGRAPH = register_scheme(StepStepgraphScheme())


# ---------------------------------------------------------------------------
# Case builder
# ---------------------------------------------------------------------------

def step_time_cases(vc, on_skip=None, schemes=None):
    """One case per (model config, step scheme) on this cluster.

    Builds the flattened-state train-step body (``runtime.steps.
    make_step_bench``), records its jaxpr link inventory on the scheme, and
    yields a ``BenchCase`` whose HLO the validate layer must match."""
    from repro.runtime.steps import make_step_bench

    for cfg_name in STEP_CONFIGS:
        cfg = get_config(cfg_name).reduced()
        for sch in _swept(registry.schemes_for("step_time"), schemes):
            body, in_specs, out_specs, make_args, elems = make_step_bench(
                cfg, vc, opts=sch.opts, unroll=cfg.n_units)
            avals = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                          for a in make_args())
            fast_b, slow_b = link_inventory(
                vc.smap(body, in_specs, out_specs), avals, vc)
            sch.record(pods=vc.pods, chips=vc.chips,
                       fast_shape=vc.fast_shape, elems=elems,
                       fast=fast_b, slow=slow_b)
            yield BenchCase(
                "step_time", sch.name, vc, elems,
                body=body, in_specs=in_specs, out_specs=out_specs,
                make_args=make_args,
                traffic=sch.traffic_for(pods=vc.pods, chips=vc.chips,
                                        fast_shape=vc.fast_shape,
                                        elems=elems))
