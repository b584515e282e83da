"""model.attn_ms: milliseconds per step that the busiest device spends in
operations under the ``attn`` scope (``models/attention.py:attn_block``):
pre-norm, q/k/v/o projections, RoPE and the KV-block scan, forward, remat
and backward (``benchmark/scopes.py``). Nothing to read where the program
names no such layer. Ops without a name of their own count where
``scopes.instructions`` places them; the ``scopes`` line gives that part as
``borrowed_ns``."""

from benchmark.scopes import layer_ms


def read(view):
    return layer_ms(view, "attn")
