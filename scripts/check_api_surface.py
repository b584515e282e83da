#!/usr/bin/env python3
"""API-surface check: collectives go through ``repro.comm``, nowhere else.

Fails (exit 1) on two kinds of bypass:

1. **Raw tier kwargs** — any module outside ``src/repro/comm/`` passing
   ``fast_axis=`` / ``slow_axis=`` keyword arguments, the old free-function
   calling convention the ``Communicator`` replaced.
2. **Raw collective primitives** — ``lax.psum(`` / ``lax.all_gather(``
   call sites outside ``repro/comm``, ``repro/substrate`` and
   ``repro/kernels``.  Raw primitives bypass scheme dispatch AND the
   step-graph optimizer (``Communicator.record()`` cannot bucket or
   reorder a collective it never sees).  Known-legitimate sites carry an
   inline ``# raw-collective: <reason>`` pragma — the tp fast paths
   (``ag_tokens`` and friends in ``models/parallel.py``, where the single
   flat tp group has exactly one schedule) and the sync primitives in
   ``core/sync.py`` the machinery itself is built from.  (The quantized
   wire formats moved INTO the registry — ``comm/quantize.py`` bodies
   behind the ``q8_hier``/``qbf16_hier``/``q4_shared`` schemes.)
3. **Deprecated compression free functions** — ``int8_bridge_psum(`` call
   sites outside ``src/repro/comm/`` and ``src/repro/optim/``: the shim
   is one-release only; new call sites go through
   ``Communicator.allreduce(..., precision="lossy")`` /
   ``reduce_grads(..., precision="lossy")``.
4. **Bare ``Communicator(...)`` in the rebuild paths** — ``src/repro/
   runtime/`` and ``src/repro/launch/`` must construct communicators only
   via ``Communicator.from_cluster`` / ``Communicator.from_topology``: a
   bare constructor there carries no static pods/chips counts, so after an
   elastic rebuild the tuning signature is unresolvable and ``scheme=
   "auto"`` silently degrades to the static fallback instead of re-tuning
   for the surviving topology.
5. **Unscoped collectives** — a raw ``lax`` collective call
   (``lax.psum(``, ``lax.all_gather(``, ``lax.ppermute(`` ...) in
   ``src/repro/comm/``, ``src/repro/core/sync.py`` or ``src/repro/models/``
   that does not go through ``repro.comm.primitives.scoped``, which names
   the collective ``comm.<primitive>[<axes>]`` in the compiled module so a
   profile can tell the on-node stage from the bridge.  No pragma excuses
   it; text between double backticks (a docstring's ``lax.psum(x, axes)``)
   is not code.

Allowed everywhere:
  * ``VirtualCluster(...)`` construction (the substrate's topology spec is
    where the axis names legitimately live);
  * ``Communicator(...)`` construction outside the rebuild paths (the tier
    spec, not a call) — inside ``repro/comm`` itself, ``models/``
    (trace-time axis wrappers), etc.;
  * annotated attribute/field definitions (``fast_axis: Axis = "data"``)
    never match the kwarg pattern.

Grep-based by design (no imports, no AST): run it anywhere, instantly.

    python scripts/check_api_surface.py [root]
"""

from __future__ import annotations

import pathlib
import re
import sys

KWARG_RE = re.compile(r"\b(?:fast_axis|slow_axis)\s*=(?!=)")
ALLOWED_LINE_RE = re.compile(r"\b(?:VirtualCluster|Communicator)\s*\(")
# a call, or the primitive handed to ``scoped(lax.psum, ...)``
RAW_RE = re.compile(r"\blax\.(?:psum|all_gather)\s*[(,]")
RAW_PRAGMA = "raw-collective:"

SCAN_ROOTS = ("src/repro", "examples")
ALLOWED_PATHS = (
    "src/repro/comm/",               # the API itself
)
RAW_ALLOWED_PATHS = (
    "src/repro/comm/",               # the primitives live here
    "src/repro/substrate/",          # compat shims wrap the primitives
    "src/repro/kernels/",            # Pallas bodies fuse their own wires
)

# every collective in these paths goes through ``primitives.scoped``
UNSCOPED_RE = re.compile(
    r"\blax\.(?:psum|pmean|pmax|pmin|all_gather|all_gather_invariant|"
    r"psum_scatter|all_to_all|ragged_all_to_all|ppermute|pshuffle|"
    r"pswapaxes|pbroadcast)\s*\(")
SCOPED_PATHS = (
    "src/repro/comm/",
    "src/repro/core/sync.py",
    "src/repro/models/",
)
LITERAL_RE = re.compile(r"``[^`]*``")

# deprecated one-release shims: no NEW call sites outside the shim's own
# module and the comm layer that implements the replacement
DEPRECATED_RE = re.compile(r"\bint8_bridge_psum\s*\(")
DEPRECATED_ALLOWED_PATHS = (
    "src/repro/comm/",
    "src/repro/optim/",
)

# bare Communicator() ctor: matches ``Communicator(`` and qualified
# ``comm.Communicator(`` but NOT the blessed ``Communicator.from_cluster(``
# / ``Communicator.from_topology(`` classmethods (a ``.`` follows the name)
CTOR_RE = re.compile(r"\bCommunicator\s*\(")
CTOR_SCAN_PATHS = (
    "src/repro/runtime/",            # elastic rebuild paths
    "src/repro/launch/",             # production launchers
)


def _scan_files(repo: pathlib.Path):
    for root in SCAN_ROOTS:
        base = repo / root
        if not base.exists():
            continue
        for path in sorted(base.rglob("*.py")):
            yield path, path.relative_to(repo).as_posix()


def kwarg_violations(repo: pathlib.Path) -> list[str]:
    out: list[str] = []
    for path, rel in _scan_files(repo):
        if any(rel.startswith(a) for a in ALLOWED_PATHS):
            continue
        depth = 0          # open-paren depth of an allowed call: its
        for lineno, line in enumerate(  # continuation lines are allowed
                path.read_text().splitlines(), start=1):
            code = line.split("#", 1)[0]
            m = ALLOWED_LINE_RE.search(code)
            if depth == 0 and m:
                # heuristic: text before the constructor and after its
                # same-line close is still checked; only the call's own
                # (possibly multi-line) argument list is exempt — a
                # violation nested INSIDE a constructor argument would
                # slip by, which AST-free grep accepts.
                if KWARG_RE.search(code[:m.start()]):
                    out.append(f"{rel}:{lineno}: {line.strip()}")
                d, end = 0, None
                for idx in range(m.start(), len(code)):
                    if code[idx] == "(":
                        d += 1
                    elif code[idx] == ")":
                        d -= 1
                        if d == 0:
                            end = idx + 1
                            break
                if end is None:          # call continues on next lines
                    depth = d
                    continue
                if KWARG_RE.search(code[end:]) and \
                        not ALLOWED_LINE_RE.search(code[end:]):
                    out.append(f"{rel}:{lineno}: {line.strip()}")
                continue
            if depth > 0:
                depth = max(depth + code.count("(") - code.count(")"), 0)
                continue
            if KWARG_RE.search(code):
                out.append(f"{rel}:{lineno}: {line.strip()}")
    return out


def raw_violations(repo: pathlib.Path) -> list[str]:
    """Raw ``lax.psum`` / ``lax.all_gather`` call sites outside the comm
    layers.  The pragma is checked on the FULL line (it lives in the
    comment the kwarg scan strips); a pragma on the line directly above
    also covers the call — the idiom when the call line has no room
    under the line-length limit."""
    out: list[str] = []
    for path, rel in _scan_files(repo):
        if any(rel.startswith(a) for a in RAW_ALLOWED_PATHS):
            continue
        lines = path.read_text().splitlines()
        for lineno, line in enumerate(lines, start=1):
            if RAW_PRAGMA in line:
                continue
            if lineno >= 2 and RAW_PRAGMA in lines[lineno - 2]:
                continue
            if RAW_RE.search(line.split("#", 1)[0]):
                out.append(f"{rel}:{lineno}: {line.strip()}")
    return out


def unscoped_violations(repo: pathlib.Path) -> list[str]:
    """Raw ``lax`` collective calls in the comm layer, the sync primitives
    and the models that bypass ``primitives.scoped``: the compiled module
    would carry no ``comm.<primitive>[<axes>]`` scope for them."""
    out: list[str] = []
    for path, rel in _scan_files(repo):
        if not any(rel.startswith(a) for a in SCOPED_PATHS):
            continue
        for lineno, line in enumerate(path.read_text().splitlines(),
                                      start=1):
            code = LITERAL_RE.sub("", line.split("#", 1)[0])
            if UNSCOPED_RE.search(code):
                out.append(f"{rel}:{lineno}: {line.strip()}")
    return out


def deprecated_violations(repo: pathlib.Path) -> list[str]:
    """Call sites of the deprecated ``optim.compression`` free functions
    outside ``repro/comm`` and ``repro/optim`` — those must migrate to the
    ``precision="lossy"`` Communicator dispatch before the shim goes."""
    out: list[str] = []
    for path, rel in _scan_files(repo):
        if any(rel.startswith(a) for a in DEPRECATED_ALLOWED_PATHS):
            continue
        for lineno, line in enumerate(path.read_text().splitlines(),
                                      start=1):
            if DEPRECATED_RE.search(line.split("#", 1)[0]):
                out.append(f"{rel}:{lineno}: {line.strip()}")
    return out


def ctor_violations(repo: pathlib.Path) -> list[str]:
    """Bare ``Communicator(...)`` constructions inside the rebuild paths
    (``runtime/``, ``launch/``) — these must go through ``from_cluster`` /
    ``from_topology`` so the static pods/chips counts (and with them the
    tuning-table signature) survive every elastic rebuild."""
    out: list[str] = []
    for path, rel in _scan_files(repo):
        if not any(rel.startswith(a) for a in CTOR_SCAN_PATHS):
            continue
        for lineno, line in enumerate(path.read_text().splitlines(),
                                      start=1):
            if CTOR_RE.search(line.split("#", 1)[0]):
                out.append(f"{rel}:{lineno}: {line.strip()}")
    return out


def violations(repo: pathlib.Path) -> list[str]:
    return kwarg_violations(repo) + raw_violations(repo) \
        + deprecated_violations(repo) + ctor_violations(repo) \
        + unscoped_violations(repo)


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    repo = pathlib.Path(args[0]) if args else \
        pathlib.Path(__file__).resolve().parent.parent
    bad_kwargs = kwarg_violations(repo)
    bad_raw = raw_violations(repo)
    bad_deprecated = deprecated_violations(repo)
    bad_ctor = ctor_violations(repo)
    bad_unscoped = unscoped_violations(repo)
    if bad_kwargs:
        print("api-surface check FAILED: raw fast_axis=/slow_axis= kwargs "
              "outside repro/comm — route these call sites through "
              "repro.comm.Communicator (README 'Communicator API'):",
              file=sys.stderr)
        for v in bad_kwargs:
            print(f"  {v}", file=sys.stderr)
    if bad_raw:
        print("api-surface check FAILED: raw lax.psum/lax.all_gather call "
              "sites outside repro/comm + repro/substrate + repro/kernels "
              "— dispatch through Communicator (so the scheme registry and "
              "the step-graph optimizer see them), or justify with an "
              "inline '# raw-collective: <reason>' pragma:",
              file=sys.stderr)
        for v in bad_raw:
            print(f"  {v}", file=sys.stderr)
    if bad_deprecated:
        print("api-surface check FAILED: deprecated int8_bridge_psum( call "
              "sites outside repro/comm + repro/optim — migrate to "
              "Communicator.allreduce(..., precision='lossy') / "
              "reduce_grads(..., precision='lossy') (the shim is "
              "one-release only):", file=sys.stderr)
        for v in bad_deprecated:
            print(f"  {v}", file=sys.stderr)
    if bad_ctor:
        print("api-surface check FAILED: bare Communicator(...) "
              "construction in the rebuild paths (src/repro/runtime, "
              "src/repro/launch) — use Communicator.from_cluster / "
              "Communicator.from_topology so static pods/chips counts "
              "(the tuning signature) survive elastic rebuilds:",
              file=sys.stderr)
        for v in bad_ctor:
            print(f"  {v}", file=sys.stderr)
    if bad_unscoped:
        print("api-surface check FAILED: raw lax collective calls in "
              "repro/comm, repro/core/sync.py or repro/models — call "
              "them through repro.comm.primitives.scoped(lax.<op>, x, "
              "axes, ...) so the compiled module names them "
              "comm.<op>[<axes>]:", file=sys.stderr)
        for v in bad_unscoped:
            print(f"  {v}", file=sys.stderr)
    if bad_kwargs or bad_raw or bad_deprecated or bad_ctor or bad_unscoped:
        return 1
    print("api-surface check OK: all collective call sites go through "
          "repro.comm")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
