"""model.moe_experts_roofline_share: the least time of one chip's grouped
expert products over the measured time of the expert part of the layer
(``model.moe_experts_ms``: the products with the sort, gathers and adds
that feed them), in %.

The least time is the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth (``benchmark/peaks.json``), both counted from the shapes by
``benchmark/experts.py:expert_work``: the rows even routing sends a chip,
through the gate, up and down products, forward and backward (a
recomputation is not counted).
Nothing to read where the program names no ``moe/experts`` part."""

from benchmark.experts import roofline_ms
from benchmark.scopes import scope_ms


def read(view):
    ms = scope_ms(view, "moe/experts")
    least = roofline_ms(view.cell, view.peak)
    if not ms or least is None:
        return None
    return 100.0 * least / ms
