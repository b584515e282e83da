"""Readings of an expert layer's cell: the grouped expert products' least
time from the shapes, and the all-to-all's time in the device trace.

:func:`expert_work` counts, from the configuration and the traffic alone,
the floating-point operations and the bytes one chip's grouped expert
products need per step: the routed rows that reach a chip under even
routing (global tokens times ``num_experts_per_tok`` over the chips), each
through the gate, up and down products of ``moe_intermediate_size``
(``2 * 3 * hidden_size * moe_intermediate_size`` FLOPs a row), and as bytes
the chip's expert weights plus the rows in and out, in float32; all of it
per layer, three times: the forward pass, and the backward pass at twice
the forward's work.  A recomputation of the forward in the backward pass
is not counted: it is not work the step needs, so a step that drops it
reads a higher share for less time, not for more work.  Routing that is
not even gives the busiest chip more rows than this.

:func:`alltoall` reduces the traced window of the busiest device to the
time in which an all-to-all is in flight (its XLA opcode, or a ``comm``
scope of ``all_to_all`` among its op_names, ``benchmark/scopes.py``) and
the part of that time in which no op that computes runs.  It reads the
newest ``.xplane.pb`` under ``.bench_trace/`` once per process, for the
busiest device alone.  Both return ``None`` where there is nothing to
read.
"""

from __future__ import annotations

import json
import re
import sys
import time

from benchmark.scopes import TRACE_DIR, _trace_module, comm_scopes

PASSES = 3          # forward, and a backward of twice the work
BYTES = 4           # float32 weights and rows


def expert_work(cell: dict) -> tuple[float, float] | None:
    """``(flops, bytes)`` per step of one chip's grouped expert products;
    ``None`` for a configuration without experts."""
    cfg, traffic = cell["config"], cell["traffic"]
    experts = cfg.get("num_experts") or cfg.get("num_local_experts")
    if not experts:
        return None
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    chips = cell["chips"]
    rows = traffic["global_batch"] * traffic["seq_len"] \
        * cfg["num_experts_per_tok"] / chips
    per_row = 2 * 3 * d * ff
    weights = experts / chips * 3 * d * ff
    layers = cfg["num_hidden_layers"]
    flops = PASSES * layers * rows * per_row
    bytes_ = PASSES * layers * BYTES * (weights + 2 * rows * d)
    return flops, bytes_


def roofline_ms(cell: dict, peak: dict) -> float | None:
    """The least time (ms per step) of one chip's grouped expert products:
    the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth."""
    work = expert_work(cell)
    if work is None:
        return None
    flops, bytes_ = work
    return 1e3 * max(flops / peak["peak_flops_bf16"],
                     bytes_ / peak["hbm_bytes_per_s"])


def is_alltoall(name: str, op: str, op_names: dict) -> bool:
    return op.startswith("all-to-all") or name.startswith("all-to-all") or \
        any(p == "all_to_all" for p, _ in comm_scopes(op_names.get(name, "")))


def alltoall_names(hlo_text: str) -> dict:
    """The op_name of each instruction of a compiled module whose op_name
    holds a ``comm.all_to_all`` scope: one pass over the text."""
    out = {}
    for line in hlo_text.splitlines():
        if "comm.all_to_all" not in line or " = " not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            name = line.strip().removeprefix("ROOT ").lstrip("%")
            out[name.partition(" = ")[0]] = m.group(1)
    return out


def reduce(trace: dict, op_names: dict, busiest: str) -> dict | None:
    """``{"inflight_ns", "exposed_ns"}`` of the all-to-alls on ``busiest``
    over the traced window; ``None`` where none ran."""
    tr = _trace_module()
    lo, hi = tr.window_of(trace["host"])
    dev = trace["devices"][busiest]
    fused = trace.get("fused") or {}
    mine = {k: [o for o in dev[k] if is_alltoall(o[0], o[1], op_names)]
            for k in ("ops", "async")}
    inflight = tr.merge(tr.collective_intervals(mine, fused), lo, hi)
    if not inflight:
        return None
    compute = tr.merge(((s, e) for name, op, _, s, e in dev["ops"]
                        if op not in tr.CONTROL and (
                            fused.get(name)
                            or not tr.collective(name, op, fused))), lo, hi)
    return {"inflight_ns": tr.length(inflight),
            "exposed_ns": tr.length(tr.subtract(inflight, compute))}


def extract_one(xplane_path: str, plane_name: str, hlo_text: str) -> dict:
    """What ``trace.extract`` gives, for the one device ``plane_name``."""
    from jax.profiler import ProfileData
    tr = _trace_module()
    devices, host = {}, []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name == plane_name:
            lines = {"XLA Ops": [], "Async XLA Ops": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] += [
                        [*tr.parse_hlo(e.name), e.start_ns,
                         e.start_ns + e.duration_ns] for e in line.events]
            devices[plane_name] = {
                "ops": sorted(lines["XLA Ops"], key=lambda o: o[3]),
                "async": sorted(lines["Async XLA Ops"], key=lambda o: o[3])}
        elif plane.name.startswith("/host:"):
            host += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(tr.HOST_PREFIX)]
    return {"devices": devices, "host": sorted(host, key=lambda s: s[1]),
            "fused": tr.hlo_collectives(hlo_text)}


def alltoall(view) -> dict | None:
    """:func:`reduce` of a traced run of ``benchmark/run.py``, once per
    process; ``None`` without a trace or an all-to-all.  It reads the
    newest ``.xplane.pb`` under ``.bench_trace/`` for the busiest device
    alone, and the module's all-to-all op_names by one pass over its
    text, and writes its time to standard error as one ``alltoall``
    line."""
    if view.trace is None:
        return None
    key = id(view.raw)
    cached = getattr(alltoall, "cache", None)
    if cached and cached[0] == key:
        return cached[1]
    t0 = time.monotonic()
    tr = _trace_module()
    hlo, busiest = view.raw["hlo_text"], view.trace["busiest"]
    trace = extract_one(tr.latest_xplane(str(TRACE_DIR)), busiest, hlo)
    out = reduce(trace, alltoall_names(hlo), busiest)
    print("alltoall " + json.dumps(
        {**(out or {}), "parse_s": time.monotonic() - t0}), file=sys.stderr)
    alltoall.cache = (key, out)
    return out
