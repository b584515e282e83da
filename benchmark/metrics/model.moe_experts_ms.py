"""model.moe_experts_ms: milliseconds per step that the busiest device
spends in operations under ``moe/experts`` (``models/moe.py``): the sort of
the received (row, expert) pairs, the gather of their rows, the grouped
gate, up and down products and the add of each output to its row, forward,
remat and backward (``benchmark/scopes.py:scope_ms``). Nothing to read where the program names
no such part of a block."""

from benchmark.scopes import scope_ms


def read(view):
    return scope_ms(view, "moe/experts")
