"""model.attn_kernel_share: the share, in %, of the ``attn`` scope's device
time on the busiest device (``model.attn_ms``'s time) that the fused
attention kernels take: the Pallas custom calls
(``custom_call_target="tpu_custom_call"``) under ``attn/flash_fwd``,
``attn/flash_dq`` and ``attn/flash_dkv`` (``models/attention.py``,
``kernels/flash_attention.py``), forward, remat and backward.  0 where
attention runs as compiler-made ops only; nothing to read where the program
names no layer, or without a trace."""

import re

from benchmark.scopes import TRACE_DIR, _trace_module, measure

KERNEL = re.compile(r"(?:^|/)attn/flash_(?:fwd|dq|dkv)(?:/|$)")


def kernels(hlo_text: str) -> set[str]:
    """The instructions of a compiled module that are the fused attention
    kernels."""
    tr = _trace_module()
    out = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, op, _ = tr.parse_hlo(line.strip().removeprefix("ROOT "))
        m = re.search(r'op_name="([^"]*)"', line)
        if op == "custom-call" and m and KERNEL.search(m.group(1)):
            out.add(name)
    return out


def read(view):
    out = measure(view)
    if out is None or not out["scoped"]["layers"] or \
            out["layers_ns"]["attn"] <= 0:
        return None
    mine = kernels(view.raw["hlo_text"])
    if not mine:
        return 0.0
    tr = _trace_module()
    trace = tr.extract(tr.latest_xplane(str(TRACE_DIR)),
                       view.raw["hlo_text"])
    lo, hi = tr.window_of(trace["host"])
    ns = sum(max(min(e, hi) - max(s, lo), 0)
             for name, _, _, s, e in trace["devices"][out["device"]]["ops"]
             if name in mine)
    return 100.0 * ns / out["layers_ns"]["attn"]
