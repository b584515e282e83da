"""Reduce a profiler trace to the benchmark's device metrics.

Two steps, kept apart so that the second can be checked on a small trace
recorded from a chip and committed beside the tests:

1. :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
   keeps what the metrics need: per device, the events of the ``XLA Ops``
   line as ``[name, opcode, type, start_ns, end_ns]`` (the event's name is
   the HLO instruction, ``%fusion.3 = f32[8]{0} fusion(...)``, cut here to
   its name, opcode and result type) and those of the ``Async XLA Ops``
   line, each spanning an asynchronous op from its start to its done; on
   the host, the benchmark's own spans (``jax.profiler.TraceAnnotation``
   names starting ``bench.``) as ``[name, start_ns, end_ns]``; and, from
   the compiled module of the traced step, the fusions that run a
   collective (``fused``, :func:`hlo_collectives`).
2. :func:`reduce` turns that into busy time, idle share, collective time and
   exposed collective time per device, and the ``breakdown`` of the result
   line, over the traced window (the ``bench.window`` span).

Collectives are found by their XLA opcodes and, for the fusions a TPU
compiler makes of them, by the compiled module (:func:`hlo_collectives`):
there an all-gather runs inside an ``async-collective-start`` ...
``async-collective-done`` pair of fusions and inside the matrix products
between them, which carry it forward, and a reduce-scatter can be one
fusion.  A synchronous collective, or a fusion that runs one, is its own
interval; an asynchronous one (``all-gather-start`` ... ``all-gather-done``,
or such a pair of fusions) is its span on the ``Async XLA Ops`` line where
that line holds it, else the interval from the start of the first op to the
end of the second.  Collective time is the union of those intervals, so
that overlapping collectives count once; exposed time is the part of it in
which no op that computes runs on that device (a fusion that carries a
collective along with a matrix product computes).  Control-flow ops
(``while``, ``conditional``, ``call``) span the ops of their bodies: they
count as busy time but not as work that hides a collective, and they are
left out of the breakdown.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all)"
    r"(-start|-done)?$")
CONTROL = ("while", "conditional", "call")
ASYNC = {"async-start": "-start", "async-done": "-done"}
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def parse_hlo(text: str) -> tuple[str, str, str]:
    """``(name, opcode, type)`` of an HLO instruction's text; the type
    without its layout, ``tuple`` for a tuple."""
    text = text.lstrip("%")
    name, sep, rest = text.partition(" = ")
    if not sep:
        return name, name.split(".")[0], ""
    if rest.startswith("("):
        depth = 0
        for j, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, rest = "tuple", rest[j + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
        typ = re.sub(r"\{[^}]*\}", "", typ)
    return name, rest.split("(")[0], typ


def extract(xplane_path: str, hlo_text: str = "") -> dict:
    """``{"devices": {plane: {"ops": [...], "async": [...]}}, "host": [...],
    "fused": {...}}``, times in ns on the trace's clock, each list sorted by
    start; ``hlo_text`` is the compiled module of the traced step."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {"XLA Ops": [], "Async XLA Ops": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] += [
                        [*parse_hlo(e.name), e.start_ns,
                         e.start_ns + e.duration_ns] for e in line.events]
            if lines["XLA Ops"]:
                devices[plane.name] = {
                    "ops": sorted(lines["XLA Ops"], key=lambda o: o[3]),
                    "async": sorted(lines["Async XLA Ops"],
                                    key=lambda o: o[3])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns,
                                     e.start_ns + e.duration_ns])
    return {"devices": devices, "host": sorted(host, key=lambda s: s[1]),
            "fused": hlo_collectives(hlo_text)}


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals, lo: float, hi: float) -> list[list[float]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``, sorted
    and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def subtract(a, b) -> list[list[float]]:
    """``a`` minus ``b``, both merged interval lists."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def collective(name: str, op: str, fused=None):
    """``(key, phase)`` of a collective op, phase ``-start``, ``-done`` or
    ``None`` for a synchronous one; ``None`` for any other op.  The two
    halves of an asynchronous collective share their key.  A collective
    wrapped in a generic ``async-start``/``async-done`` pair is known by its
    name, a fusion that runs one by ``fused`` (:func:`hlo_collectives`)."""
    m = COLLECTIVE.match(op)
    if not m and op in ASYNC:
        m = COLLECTIVE.match(re.split(r"[.-](?=start|done|\d)", name)[0])
    if not m and not (fused and name in fused):
        return None
    phase = re.search(r"-(start|done)\b", name)
    if phase is None:
        return name, None
    return name[:phase.start()] + name[phase.end():], "-" + phase.group(1)


def collective_intervals(dev: dict, fused=None) -> list[tuple[float, float]]:
    """Intervals in which a collective is in flight on one device."""
    out, spanned = [], set()
    for name, op, _, s, e in dev["async"]:
        c = collective(name, op, fused)
        if c and c[1] == "-start":
            out.append((s, e))
            spanned.add(c[0])
    open_starts: dict[str, list[float]] = {}
    for name, op, _, s, e in dev["ops"]:
        c = collective(name, op, fused)
        if not c or c[0] in spanned:
            continue
        key, phase = c
        if phase is None:
            out.append((s, e))
        elif phase == "-start":
            open_starts.setdefault(key, []).append(s)
        else:
            starts = open_starts.get(key)
            out.append((starts.pop(0) if starts else s, e))
    return out


def hlo_collectives(text: str) -> dict[str, bool]:
    """The instructions of a compiled HLO module (``Compiled.as_text()``)
    that call a computation running a collective, each mapped to whether
    that computation also multiplies matrices (``dot`` or
    ``convolution``), directly or through the computations it calls."""
    ops: dict[str, set] = {}
    callees: dict[str, set] = {}
    callers: dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        if not line[:1].isspace():
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", line)
            comp = m.group(1) if m and line.rstrip().endswith("{") else None
            if comp:
                ops[comp], callees[comp] = set(), set()
            continue
        if comp is None or " = " not in line:
            continue
        name, op, _ = parse_hlo(line.strip().removeprefix("ROOT "))
        ops[comp].add(op)
        m = re.search(r"\bcalls=%([\w.\-]+)", line)
        if m:
            callees[comp].add(m.group(1))
            callers[name] = m.group(1)

    def holds(comp, want, seen=()):
        if comp in seen or comp not in ops:
            return False
        return any(want(o) for o in ops[comp]) or any(
            holds(c, want, seen + (comp,)) for c in callees[comp])

    return {name: holds(comp, lambda o: o in ("dot", "convolution"))
            for name, comp in callers.items()
            if holds(comp, lambda o: bool(COLLECTIVE.match(o)))}


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def window_of(host) -> tuple[float, float]:
    spans = [s for s in host if s[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    return spans[-1][1], spans[-1][2]


def device_summary(dev: dict, lo: float, hi: float, fused=None) -> dict:
    """Busy, collective and exposed collective time (ns) of one device."""
    ops = dev["ops"]
    fused = fused or {}
    busy = merge(((s, e) for *_, s, e in ops), lo, hi)
    coll = merge(collective_intervals(dev, fused), lo, hi)
    compute = merge(((s, e) for name, op, _, s, e in ops
                     if op not in CONTROL and (
                         fused.get(name) or not collective(name, op, fused))),
                    lo, hi)
    return {"busy": busy, "busy_ns": length(busy),
            "collective_ns": length(coll),
            "exposed_ns": length(subtract(coll, compute))}


def _label(host, t: float) -> str:
    """The innermost benchmark span that covers time ``t``."""
    best = None
    for name, s, e in host:
        if s <= t <= e and name != WINDOW_SPAN:
            if best is None or e - s < best[1]:
                best = (name, e - s)
    return best[0] if best else "no host span"


def reduce(trace: dict, *, top: int = 10) -> dict:
    """Device metrics over the traced window.

    Returns ``window_ns``, ``devices`` (per plane: busy, collective and
    exposed ns), ``busiest`` (the plane with the most busy time),
    ``busy_mean_ns`` over the devices, and ``breakdown`` with the
    ``device_ops`` that took most time on the busiest device and its longest
    ``idle_gaps``, each labelled by what the host was doing in it."""
    lo, hi = window_of(trace["host"])
    if not trace["devices"]:
        raise ValueError("the trace holds no device ops")
    per = {p: device_summary(ops, lo, hi, trace.get("fused"))
           for p, ops in trace["devices"].items()}
    busiest = max(per, key=lambda p: per[p]["busy_ns"])
    totals: dict[str, float] = {}
    for name, op, typ, s, e in trace["devices"][busiest]["ops"]:
        d = min(e, hi) - max(s, lo)
        if d > 0 and op not in CONTROL:
            key = f"{name} {typ}"
            totals[key] = totals.get(key, 0.0) + d
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    busy = per[busiest]["busy"]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_ns": hi - lo,
        "devices": {p: {k: v for k, v in d.items() if k != "busy"}
                    for p, d in per.items()},
        "busiest": busiest,
        "busy_mean_ns": sum(d["busy_ns"] for d in per.values()) / len(per),
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[_label(trace["host"], (s + e) / 2), (e - s) / 1e9]
                          for s, e in gaps],
        },
    }
