"""model.mlp_ms: milliseconds per step that the busiest device spends in
operations under the ``mlp`` scope (``models/layers.py:ffn``): pre-norm,
in/gate/out projections and the activation, forward, remat and backward
(``benchmark/scopes.py``). Nothing to read where the program names no
such layer. Ops without a name of their own count where ``scopes.instructions``
places them; the ``scopes`` line gives that part as ``borrowed_ns``."""

from benchmark.scopes import layer_ms


def read(view):
    return layer_ms(view, "mlp")
