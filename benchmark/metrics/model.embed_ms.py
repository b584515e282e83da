"""model.embed_ms: milliseconds per step that the busiest device spends in
operations under the ``embed`` scope (``models/transformer.py:_embed_sp``):
the lookup and the input scaling, and in the backward the embedding
gradient's scatter-add (``benchmark/scopes.py``). Nothing to read where the
program names no such layer. Ops without a name of their own count where
``scopes.instructions`` places them; the ``scopes`` line gives that part as
``borrowed_ns``."""

from benchmark.scopes import layer_ms


def read(view):
    return layer_ms(view, "embed")
