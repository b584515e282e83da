"""Matrix-driven collective benchmark suites.

Every case is one (family, scheme, topology, message size) cell:

* families — ``allgather``, ``broadcast``, ``psum`` (paper §4.1/4.2 and the
  gradient-reduction analogue), ``reduce_scatter``, ``allgatherv``
  (irregularly populated nodes, paper Figs 4/10) and ``alltoall``
  (personalized exchange: flat vs node-aware two-phase schedule);
* schemes  — whatever the ``repro.comm`` registry declares for the family
  (today ``naive``/``hier``/``shared``/``pipelined``): cases are built by
  sweeping ``registry.schemes_for(family)`` and dispatching through a
  ``Communicator``, so registering a new scheme adds it to the sweep with
  no edits here.  A scheme whose tunable grid is empty for a cell (its
  tiling divisor does not divide ``elems`` on that topology) is
  skipped-and-logged, never raised — irregular sizes can enter the sweep;
* tunables — a scheme's ``candidates()`` grid (e.g. ``pipelined``'s
  ``n_chunks``) is autotuned per (topology, size) cell: every candidate is
  compiled, cross-checked and timed, and the best median is the recorded
  number (the full sweep lands in the JSON's ``autotune`` record);
* topologies — ``repro.substrate.default_matrix()``: 1x8, 2x4, 4x2, 8x1 and
  the tuple-axis ``pod x (dp, tp)`` mesh.

A case AOT-compiles once per candidate (``jit(...).lower(...).compile()``);
the same executable is timed by ``run_suite``'s interleaved round-robin
loop (``runner.timed_call``/``summarize``) *and* its HLO text is what
``validate`` cross-checks against the scheme's self-described traffic model.
Inputs are ``device_put`` onto the cluster mesh before timing, so
host-to-device transfer never lands inside the timed region.
"""

from __future__ import annotations

import dataclasses
import random
import re
import time
import warnings
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.bench import runner
from repro.comm import Communicator, SharedWindow, registry
from repro.core.plans import CollectiveTraffic, GatherPlan, NodeMap
from repro.substrate import VirtualCluster, default_matrix

ELEM_BYTES = 4  # the default float32 payload (NOT float64 — the x64-disabled
                # downcast warning of the seed bench came from f64 arange)
ELEM_DTYPE = "float32"  # recorded per case: the tuning table keys by dtype

# families swept at extra dtypes (``--dtypes float32,bfloat16``): the
# gradient-reduction and weight-window payloads whose wire format the
# quantized schemes compress — a bf16 sweep lets the tuning table
# discriminate by dtype (an int8 wire buys ~4x over f32 but only ~2x over
# bf16, so the ranking can legitimately flip).
DTYPE_SWEPT = ("allgather", "psum")


def _dtype_bytes(dtype: str) -> int:
    """Per-element bytes of a named jnp dtype (handles bfloat16, which
    plain ``np.dtype(str)`` does not know)."""
    return int(np.dtype(getattr(jnp, dtype)).itemsize)


def _wire_bytes(dtype: str) -> int:
    """Per-element bytes the payload occupies ON THE WIRE in the compiled
    artifact.  XLA's CPU backend normalizes sub-f32 *float* collectives to
    f32 (``convert -> f32 collective -> convert``), so a bf16 payload
    crosses links at 4 bytes there — the link-byte expectations must price
    the artifact, not the logical dtype.  Integer wires (the quantized
    schemes' codes, incl. the bitcast-u16 bf16 wire) lower natively on
    every backend and are priced inside each scheme's ``links`` closed
    form, independent of this payload width."""
    eb = _dtype_bytes(dtype)
    if eb < 4 and jax.default_backend() == "cpu" and \
            jnp.issubdtype(getattr(jnp, dtype), jnp.floating):
        return 4
    return eb

def _case_traffic(sch, family: str, vc, elems: int, dtype: str,
                  **kw) -> CollectiveTraffic:
    """The scheme's traffic model for one case: wire bytes priced at the
    COMPILED width (``_wire_bytes`` — the HLO cross-check target), the
    resident result at the LOGICAL dtype width (output shards really are
    e.g. bf16 even when the CPU backend widens the wire)."""
    web, eb = _wire_bytes(dtype), _dtype_bytes(dtype)
    tr = sch.traffic(family, pods=vc.pods, chips=vc.chips, elems=elems,
                     elem_bytes=web, **kw)
    if eb == web:
        return tr
    res = sch.traffic(family, pods=vc.pods, chips=vc.chips, elems=elems,
                      elem_bytes=eb, **kw)
    return CollectiveTraffic(
        slow_bytes=tr.slow_bytes, fast_bytes=tr.fast_bytes,
        result_bytes_per_node=res.result_bytes_per_node)


FAMILIES = ("allgather", "broadcast", "psum", "reduce_scatter",
            "allgatherv", "alltoall", "step_time", "serving")
# families that size themselves per cluster (one sweep per topology,
# outside the message-size loop) and register their schemes on import
SELF_SIZED = ("step_time", "serving")
# QUICK_ELEMS must stay a subset of FULL_ELEMS: CI's perf-regression gate
# compares the quick sweep against a committed full-sweep baseline, and
# only shared (family, scheme, topology, elems) cells can be compared.
FULL_ELEMS = (256, 1024, 4096, 65536)
QUICK_ELEMS = (1024,)
assert set(QUICK_ELEMS) <= set(FULL_ELEMS)


def slug(s: str) -> str:
    """CSV-safe case name (matches ``^[a-z0-9_]+``)."""
    return re.sub(r"[^a-z0-9]+", "_", s.lower()).strip("_")


def _raw(out):
    """Bench bodies return arrays: unwrap shared-scheme windows."""
    return out.shard if isinstance(out, SharedWindow) else out


@dataclasses.dataclass
class BenchCase:
    """One measurable config: a shard_map body bound to a cluster + inputs
    + the registry-supplied traffic model it must agree with.

    ``tunable_grid`` holds the scheme's autotune candidates for this cell
    (``({},)`` = untunable); ``body_with(kwargs)`` builds the body for one
    candidate (``body`` is the default-candidate body)."""

    family: str
    scheme: str                      # a repro.comm registry entry name
    cluster: VirtualCluster
    elems: int                       # per-rank / message / per-pair elems
    body: Callable
    in_specs: tuple
    out_specs: object
    make_args: Callable[[], tuple]
    traffic: CollectiveTraffic       # scheme.traffic(...) for this config
    plan: Optional[GatherPlan] = None        # allgatherv only
    populations: Optional[tuple] = None      # allgatherv only
    body_with: Optional[Callable[[dict], Callable]] = None
    tunable_grid: tuple = ({},)
    dtype: str = ELEM_DTYPE          # payload dtype (wire-format sweeps)

    @property
    def topology(self) -> str:
        return self.cluster.label

    @property
    def elem_bytes(self) -> int:
        """Logical per-element bytes (result layouts, tuning-table keys)."""
        return _dtype_bytes(self.dtype)

    @property
    def wire_elem_bytes(self) -> int:
        """Per-element bytes on the compiled wire (see ``_wire_bytes``)."""
        return _wire_bytes(self.dtype)

    @property
    def name(self) -> str:
        # f32 names stay unsuffixed so the CI regression gate's committed
        # baseline cells keep matching across the dtype-sweep introduction
        base = f"{self.family}/{self.scheme}/{self.topology}/e{self.elems}"
        return base if self.dtype == ELEM_DTYPE else f"{base}/{self.dtype}"

    @property
    def csv_name(self) -> str:
        base = f"{self.family}_{self.scheme}_{self.topology}_{self.elems}"
        if self.dtype != ELEM_DTYPE:
            base = f"{base}_{self.dtype}"
        return slug(base)

    def compile(self, tunable: Optional[dict] = None):
        """AOT-compile on the cluster mesh (one tunable candidate).
        Returns ``(compiled, args)`` with ``args`` already device_put to
        the in_specs shardings."""
        body = self.body if not tunable and self.body_with is None \
            else self.body_with(dict(tunable or {}))
        mesh = self.cluster.mesh
        f = jax.jit(self.cluster.smap(body, self.in_specs,
                                      self.out_specs))
        args = tuple(
            jax.device_put(a, NamedSharding(mesh, s))
            for a, s in zip(self.make_args(), self.in_specs))
        return f.lower(*args).compile(), args


def _ranked_f32(num: int) -> jax.Array:
    return jnp.arange(num, dtype=jnp.float32)


def _ranked(num: int, dtype: str) -> jax.Array:
    """Ranked payload in the case dtype (built in f32, downcast once, so
    the bf16 sweep measures a bf16 wire, not an f32 arange side effect)."""
    return _ranked_f32(num).astype(getattr(jnp, dtype))


# ---------------------------------------------------------------------------
# Family builders (one BenchCase per registered scheme)
# ---------------------------------------------------------------------------

def _swept(schs, schemes):
    """Registry entries filtered to an explicit scheme subset (None = all):
    excluded schemes are never built and never logged as skipped."""
    if schemes is None:
        return schs
    return tuple(s for s in schs if s.name in schemes)


class BenchCoverageWarning(UserWarning):
    """A (family, scheme, topology, size) cell was dropped from the sweep
    (size does not tile for the scheme) — coverage, not correctness."""


def _grid_or_skip(sch, family: str, vc: VirtualCluster, elems: int,
                  on_skip) -> tuple:
    """The scheme's tunable grid for one cell; empty = skip-and-log (the
    cell's size does not tile on this topology for this scheme).  With no
    ``on_skip`` logger the drop still surfaces as a
    ``BenchCoverageWarning`` — never a fully silent coverage loss."""
    grid = sch.candidates(family, pods=vc.pods, chips=vc.chips, elems=elems)
    if not grid:
        need = sch.tiling(family, pods=vc.pods, chips=vc.chips)
        msg = (f"skip {family}/{sch.name}/{vc.label}/e{elems}: "
               f"elems={elems} does not tile by {need} "
               f"(scheme tiling divisor on this topology)")
        if on_skip is not None:
            on_skip(msg)
        else:
            warnings.warn(msg, BenchCoverageWarning, stacklevel=3)
    return grid


def allgather_cases(vc: VirtualCluster, elems: int, on_skip=None,
                    schemes=None, dtype: str = ELEM_DTYPE):
    comm = Communicator.from_cluster(vc)
    R = vc.num_devices

    def args():
        return (_ranked(R * elems, dtype),)

    for sch in _swept(registry.schemes_for("allgather"), schemes):
        grid = _grid_or_skip(sch, "allgather", vc, elems, on_skip)
        if not grid:
            continue
        out_specs = P(None) if sch.result_class == "replicated" else vc.spec

        # a concretely-named lossy scheme must opt in (Communicator raises
        # otherwise); exact schemes keep the default constraint
        def body_with(opts, s=sch.name, p=sch.precision):
            return lambda v: _raw(comm.allgather(v, scheme=s, precision=p,
                                                 **opts))

        yield BenchCase(
            "allgather", sch.name, vc, elems,
            body=body_with({}),
            in_specs=(vc.spec,), out_specs=out_specs, make_args=args,
            traffic=_case_traffic(sch, "allgather", vc, elems, dtype),
            body_with=body_with, tunable_grid=grid, dtype=dtype)


def broadcast_cases(vc: VirtualCluster, elems: int, on_skip=None,
                    schemes=None, dtype: str = ELEM_DTYPE):
    comm = Communicator.from_cluster(vc)
    R = vc.num_devices
    root = R // 2          # a non-zero, non-leader root: the flat-root API

    def args():
        return (_ranked(R * elems, dtype).reshape(R, elems),)

    for sch in _swept(registry.schemes_for("broadcast"), schemes):
        grid = _grid_or_skip(sch, "broadcast", vc, elems, on_skip)
        if not grid:
            continue
        out_specs = P(None) if sch.result_class == "replicated" \
            else P(vc.fast)

        def body_with(opts, s=sch.name, p=sch.precision):
            return lambda v: _raw(comm.broadcast(v[0], root=root, scheme=s,
                                                 precision=p, **opts))

        yield BenchCase(
            "broadcast", sch.name, vc, elems,
            body=body_with({}),
            in_specs=(vc.spec,), out_specs=out_specs, make_args=args,
            traffic=_case_traffic(sch, "broadcast", vc, elems, dtype),
            body_with=body_with, tunable_grid=grid, dtype=dtype)


def psum_cases(vc: VirtualCluster, elems: int, on_skip=None,
               schemes=None, dtype: str = ELEM_DTYPE):
    comm = Communicator.from_cluster(vc)
    R = vc.num_devices

    def args():
        # scaled so the reduction stays well inside f32 range (built in
        # f32, then downcast to the case dtype)
        return ((_ranked_f32(R * elems).reshape(R, elems) / (R * elems))
                .astype(getattr(jnp, dtype)),)

    for sch in _swept(registry.schemes_for("psum"), schemes):
        grid = _grid_or_skip(sch, "psum", vc, elems, on_skip)
        if not grid:
            continue
        out_specs = P(None) if sch.result_class == "replicated" \
            else P(vc.fast)

        def body_with(opts, s=sch.name, p=sch.precision):
            return lambda v: _raw(comm.allreduce(v[0], scheme=s,
                                                 precision=p, **opts))

        yield BenchCase(
            "psum", sch.name, vc, elems,
            body=body_with({}),
            in_specs=(vc.spec,), out_specs=out_specs, make_args=args,
            traffic=_case_traffic(sch, "psum", vc, elems, dtype),
            body_with=body_with, tunable_grid=grid, dtype=dtype)


def reduce_scatter_cases(vc: VirtualCluster, elems: int, on_skip=None,
                         schemes=None, dtype: str = ELEM_DTYPE):
    """Every rank contributes a full ``elems`` buffer; the global sum is
    scattered.  ``naive``/``pipelined`` end with flat 1/R slices
    (rank-major); ``shared`` keeps the node's reduced message once,
    sharded over the window."""
    comm = Communicator.from_cluster(vc)
    R = vc.num_devices

    def args():
        return ((_ranked_f32(R * elems).reshape(R, elems) / (R * elems))
                .astype(getattr(jnp, dtype)),)

    for sch in _swept(registry.schemes_for("reduce_scatter"), schemes):
        grid = _grid_or_skip(sch, "reduce_scatter", vc, elems, on_skip)
        if not grid:
            continue
        out_specs = P(vc.axis_names) if sch.result_class == "replicated" \
            else P(vc.fast)

        def body_with(opts, s=sch.name, p=sch.precision):
            return lambda v: _raw(comm.reduce_scatter(v[0], scheme=s,
                                                      precision=p, **opts))

        yield BenchCase(
            "reduce_scatter", sch.name, vc, elems,
            body=body_with({}),
            in_specs=(vc.spec,), out_specs=out_specs, make_args=args,
            traffic=_case_traffic(sch, "reduce_scatter", vc, elems,
                                  dtype),
            body_with=body_with, tunable_grid=grid, dtype=dtype)


def alltoall_cases(vc: VirtualCluster, elems: int, on_skip=None,
                   schemes=None, dtype: str = ELEM_DTYPE):
    """Personalized exchange: every rank holds R rank-ordered chunks of
    ``elems`` each; chunk *s* goes to rank *s* (flat vs node-aware)."""
    comm = Communicator.from_cluster(vc)
    R = vc.num_devices

    def args():
        return (_ranked(R * R * elems, dtype),)

    for sch in _swept(registry.schemes_for("alltoall"), schemes):
        grid = _grid_or_skip(sch, "alltoall", vc, elems, on_skip)
        if not grid:
            continue

        def body_with(opts, s=sch.name, p=sch.precision):
            return lambda v: comm.alltoall(v, scheme=s, precision=p, **opts)

        yield BenchCase(
            "alltoall", sch.name, vc, elems,
            body=body_with({}),
            in_specs=(vc.spec,), out_specs=vc.spec, make_args=args,
            traffic=_case_traffic(sch, "alltoall", vc, elems, dtype),
            body_with=body_with, tunable_grid=grid, dtype=dtype)


def bench_populations(pods: int, chips: int) -> tuple[int, ...]:
    """Deterministic irregular node populations: node k holds
    ``chips - (k % chips)`` ranks (always >= 1, node 0 always full)."""
    return tuple(chips - (k % chips) for k in range(pods))


def allgatherv_cases(vc: VirtualCluster, max_elems: int,
                     populations=None, on_skip=None, schemes=None,
                     dtype: str = ELEM_DTYPE):
    comm = Communicator.from_cluster(vc)
    R = vc.num_devices
    pops = tuple(populations) if populations is not None \
        else bench_populations(vc.pods, vc.chips)
    plan = GatherPlan(NodeMap.irregular(list(pops)), elem_per_rank=max_elems)
    plan.check()

    def args():
        data = np.arange(R * max_elems,
                         dtype=np.float32).reshape(R, max_elems)
        valid = np.zeros((R, 1), np.int32)
        for pd in range(vc.pods):
            for i in range(vc.chips):
                r = pd * vc.chips + i
                valid[r, 0] = max_elems if i < pops[pd] else 0
                if i >= pops[pd]:
                    data[r] = 0.0
        return (jnp.asarray(data).astype(getattr(jnp, dtype)),
                jnp.asarray(valid))

    # the naive scheme gathers the padded blocks AND the counts flat (an MPI
    # allgatherv still exchanges counts), so the two schemes move the same
    # *kinds* of payload and C1 stays an exact shard-level ratio.
    for sch in _swept(registry.schemes_for("allgatherv"), schemes):
        grid = _grid_or_skip(sch, "allgatherv", vc, max_elems, on_skip)
        if not grid:
            continue
        out_specs = (P(None), P(None)) if sch.result_class == "replicated" \
            else (P(None, vc.fast), P(None, vc.fast))

        def body_with(opts, s=sch.name, p=sch.precision):
            return lambda v, val: comm.allgatherv(v, val, scheme=s,
                                                  precision=p, **opts)

        yield BenchCase(
            "allgatherv", sch.name, vc, max_elems,
            body=body_with({}),
            in_specs=(vc.spec, vc.spec), out_specs=out_specs,
            make_args=args,
            traffic=_case_traffic(sch, "allgatherv", vc, max_elems, dtype,
                                  populations=pops),
            plan=plan, populations=pops,
            body_with=body_with, tunable_grid=grid, dtype=dtype)


def step_time_cases(vc: VirtualCluster, elems=None, on_skip=None,
                    schemes=None):
    """Bridge to ``repro.bench.step_time``: whole-train-step cases.  The
    family sizes itself (``elems`` is each model config's global parameter
    element count), so ``build_cases`` invokes it once per cluster, outside
    the message-size sweep."""
    from repro.bench import step_time as st
    return st.step_time_cases(vc, on_skip=on_skip, schemes=schemes)


def serving_cases(vc: VirtualCluster, elems=None, on_skip=None,
                  schemes=None):
    """Bridge to ``repro.bench.serving``: continuous-batching decode-step
    cases (self-sized per cluster, like ``step_time``)."""
    from repro.bench import serving as sv
    return sv.serving_cases(vc, on_skip=on_skip, schemes=schemes)


_FAMILY_BUILDERS = {
    "allgather": allgather_cases,
    "broadcast": broadcast_cases,
    "psum": psum_cases,
    "reduce_scatter": reduce_scatter_cases,
    "allgatherv": allgatherv_cases,
    "alltoall": alltoall_cases,
    "step_time": step_time_cases,
    "serving": serving_cases,
}


def build_cases(*, clusters: Optional[Sequence[VirtualCluster]] = None,
                families: Sequence[str] = FAMILIES,
                elems: Sequence[int] = FULL_ELEMS,
                max_devices: int = 8,
                schemes: Optional[Sequence[str]] = None,
                dtypes: Sequence[str] = (ELEM_DTYPE,),
                on_skip=None) -> list[BenchCase]:
    """The sweep: topology matrix x families x message sizes (x dtypes).

    ``schemes`` filters to a subset of registry entries (fast autotune
    iteration: ``--schemes pipelined,hier``); ``on_skip`` receives one
    message per (family, scheme, topology, size) cell whose size does not
    tile for that scheme — such cells are skipped, never raised.
    ``dtypes`` widens the sweep beyond float32 for the ``DTYPE_SWEPT``
    families only (the wire-format-sensitive payloads); other families run
    at float32 regardless.
    """
    if clusters is None:
        clusters = default_matrix(max_devices)
    unknown = set(families) - set(_FAMILY_BUILDERS)
    if unknown:
        raise ValueError(f"unknown families {sorted(unknown)}; "
                         f"pick from {list(_FAMILY_BUILDERS)}")
    for dt in dtypes:
        if not hasattr(jnp, dt):
            raise ValueError(f"unknown dtype {dt!r}: not a jax.numpy "
                             f"dtype name (try float32, bfloat16)")
    if "step_time" in families:
        from repro.bench import step_time  # noqa: F401  registers its
        # eager/prefetch schemes before the scheme-name validation below
    if "serving" in families:
        from repro.bench import serving  # noqa: F401  registers sync/
        # recorded before the scheme-name validation below
    if schemes is not None:
        if "auto" in schemes:
            raise ValueError(
                "'auto' is the tuning-table dispatch mode, not a registry "
                "entry — the sweep measures the concrete schemes auto "
                "chooses between (emit the table from the sweep instead: "
                "python -m repro.bench --emit-tuning-table)")
        unknown_s = set(schemes) - set(registry.scheme_names())
        if unknown_s:
            raise ValueError(f"unknown schemes {sorted(unknown_s)}; "
                             f"registered: {list(registry.scheme_names())}")
    cases: list[BenchCase] = []
    per_size = tuple(f for f in families if f not in SELF_SIZED)
    for vc in clusters:
        for dt in dict.fromkeys(dtypes):   # de-duped, order-preserving
            fams = per_size if dt == ELEM_DTYPE else \
                tuple(f for f in per_size if f in DTYPE_SWEPT)
            for e in elems:
                for fam in fams:
                    cases.extend(_FAMILY_BUILDERS[fam](
                        vc, e, on_skip=on_skip, schemes=schemes, dtype=dt))
        for fam in SELF_SIZED:
            if fam in families:
                # self-sized family: one sweep per cluster, not per size
                cases.extend(_FAMILY_BUILDERS[fam](vc, on_skip=on_skip,
                                                   schemes=schemes))
    return cases


# ---------------------------------------------------------------------------
# Suite execution
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CaseResult:
    case: BenchCase
    timing: runner.TimingResult
    hlo: dict                    # parsed link/result bytes (validate.py)
    checks: list                 # per-case validate.Check list
    autotune: Optional[dict] = None   # tunable sweep record (best wins)


def _cand_tag(cand: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(cand.items()))


class _Entry(NamedTuple):
    """One compiled (case, tunable-candidate) executable in a timing cell."""
    case: BenchCase
    cand: dict
    compiled: object
    args: tuple
    hlo: dict
    checks: list
    inner: int
    canon: str          # program identity: HLO minus source metadata


@dataclasses.dataclass
class SuiteResult:
    cases: list[CaseResult]
    cross_checks: list           # cross-scheme C1 validate.Check list


def run_suite(cases: Sequence[BenchCase], *, reps: int = 30,
              min_rep_s: float = 0.0, validate: bool = True,
              log=None) -> SuiteResult:
    """Compile, measure and cross-check every case.

    A case with a tunable grid (e.g. ``pipelined``'s ``n_chunks``) is
    autotuned: EVERY candidate is compiled, cross-checked (the closed forms
    are tunable-invariant — chunking must not change the total bytes) and
    timed with the same reps; the best median is the case's recorded
    number and the full sweep lands in ``CaseResult.autotune``.

    Timing is **interleaved per cell**: all (case, candidate) executables
    of one (family, topology, size) group are timed round-robin — rep *r*
    of every entry runs back-to-back before rep *r+1* of any — so the
    scheme-vs-scheme and candidate-vs-candidate comparisons the sweep
    exists for share one machine-drift profile instead of each entry
    meeting a different moment of a noisy host.  The within-round order is
    shuffled per round (fixed seed — deterministic sweeps) so no entry
    systematically inherits a fixed neighbor's cache/thermal state.
    Entries within a cell whose compiled programs are IDENTICAL modulo
    source metadata (e.g. ``pipelined`` at ``n_chunks=1`` vs ``hier`` —
    one chunk is the unchunked schedule) and share the same calibrated
    inner count are measurements of one program: their samples are pooled,
    so they report one median instead of two allocation-luck-separated
    numbers for the same executable.  A pooled case's ``timing.reps`` is
    the POOLED sample count backing its statistics (a multiple of the
    requested reps).

    Per-case and cross-scheme (C1) validation failures are collected and
    raised together as ``validate.BenchValidationError`` AFTER the whole
    sweep ran, so one bad config reports alongside the full picture.
    """
    from repro.bench import validate as V

    def _canon(hlo_text: str) -> str:
        # program identity: the compiled module minus source metadata
        return re.sub(r"metadata=\{[^}]*\}", "", hlo_text)

    # preserve input order while grouping into comparison cells
    groups: dict[tuple, list[BenchCase]] = {}
    for case in cases:
        groups.setdefault(
            (case.family, case.topology, case.elems, case.dtype),
            []).append(case)

    results_by_id: dict[int, CaseResult] = {}
    done = 0
    for group in groups.values():
        # phase 1 — compile every (case, candidate); the one inspection
        # execution IS the timer's warmup: its outputs feed the
        # shard-level result-bytes measurement
        entries: list[_Entry] = []
        for case in group:
            if not case.cluster.available():
                raise RuntimeError(
                    f"{case.name}: needs {case.cluster.num_devices} "
                    f"devices, have {jax.device_count()} — force more host "
                    "devices (see repro.substrate."
                    "ensure_host_device_count)")
            for cand in tuple(case.tunable_grid) or ({},):
                compiled, args = case.compile(cand)
                t0 = time.perf_counter()
                outputs = runner.block_all(compiled(*args))
                warm_s = time.perf_counter() - t0
                hlo_text = compiled.as_text()
                hlo_meas, checks = V.inspect_case(case, hlo_text, outputs,
                                                  opts=cand)
                entries.append(_Entry(
                    case=case, cand=cand, compiled=compiled, args=args,
                    hlo=hlo_meas, checks=checks,
                    inner=runner.calibrate_inner(warm_s, min_rep_s),
                    canon=_canon(hlo_text)))
        # identical programs must share ONE calibration, or warmup jitter
        # could split their pools (same canon, different inner)
        min_inner: dict[str, int] = {}
        for e in entries:
            min_inner[e.canon] = min(min_inner.get(e.canon, e.inner),
                                     e.inner)
        entries = [e._replace(inner=min_inner[e.canon]) for e in entries]
        # phase 2 — interleaved round-robin timing over the cell; the
        # within-round order is re-shuffled each round (fixed seed) so no
        # entry always follows the same neighbor
        rng = random.Random(0x5EED)
        samples: list[list[float]] = [[] for _ in entries]
        order = list(range(len(entries)))
        for _ in range(reps):
            rng.shuffle(order)
            for i in order:
                e = entries[i]
                samples[i].append(runner.timed_call(e.compiled, *e.args,
                                                    inner=e.inner))
        # pool samples of program-identical entries (same canonical HLO +
        # same inner calibration = the same executable measured under two
        # labels; per-call microseconds, so pooling is unit-consistent)
        by_prog: dict[tuple, list[float]] = {}
        for i, e in enumerate(entries):
            by_prog.setdefault((e.canon, e.inner), []).extend(samples[i])
        pooled = [by_prog[(e.canon, e.inner)] for e in entries]
        # phase 3 — aggregate per case: best candidate wins
        for case in group:
            tuned = [(e.cand, runner.summarize(pooled[i], inner=e.inner),
                      e.hlo, e.checks)
                     for i, e in enumerate(entries) if e.case is case]
            best = min(tuned, key=lambda t: t[1].median_us)
            checks = list(best[3])
            for cand, _, _, cand_checks in tuned:
                if cand is best[0]:
                    continue
                # non-best candidates contribute only their FAILURES
                # (tagged): the closed forms are tunable-invariant, so a
                # pass adds no news
                checks.extend(
                    dataclasses.replace(ch,
                                        name=f"{ch.name}@{_cand_tag(cand)}")
                    for ch in cand_checks if not ch.ok)
            autotune = None
            if len(tuned) > 1 or tuned[0][0]:
                autotune = {
                    "param_grid": [dict(c) for c, _, _, _ in tuned],
                    "results": [{**dict(c), "median_us": t.median_us}
                                for c, t, _, _ in tuned],
                    "best": dict(best[0]),
                }
            results_by_id[id(case)] = CaseResult(
                case, best[1], best[2], checks if validate else [],
                autotune)
            done += 1
            if log:
                tag = f" [{_cand_tag(best[0])}]" if best[0] else ""
                log(f"[{done}/{len(cases)}] {case.name}{tag}: "
                    f"{best[1].median_us:.1f}us (iqr "
                    f"{best[1].iqr_us:.1f}, {len(tuned)} candidate(s))")
    results = [results_by_id[id(c)] for c in cases]
    cross = V.cross_scheme_checks(results) if validate else []
    if validate:
        V.raise_on_failure(results, cross)
    return SuiteResult(results, cross)
