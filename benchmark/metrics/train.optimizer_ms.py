"""train.optimizer_ms: milliseconds per step that the busiest device spends in
operations under the ``optimizer`` scope (``runtime/steps.py``, after the
gradient exchange): the division by the token count, the global gradient
norm, the clip and AdamW (``benchmark/scopes.py``). Nothing to read where
the program names no such layer. Ops without a name of their own count where
``scopes.instructions`` places them; the ``scopes`` line gives that part as
``borrowed_ns``."""

from benchmark.scopes import layer_ms


def read(view):
    return layer_ms(view, "optimizer")
