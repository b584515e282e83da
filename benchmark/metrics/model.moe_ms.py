"""model.moe_ms: milliseconds per step that the busiest device spends in
operations under the ``moe`` scope (``models/moe.py:moe_block``): pre-norm,
router, dispatch, the grouped expert products and the combine, forward,
remat and backward (``benchmark/scopes.py``); the all-to-alls themselves
are collectives and count under ``comm.alltoall_ms``. Nothing to read where
the program names no such layer."""

from benchmark.scopes import layer_ms


def read(view):
    return layer_ms(view, "moe")
