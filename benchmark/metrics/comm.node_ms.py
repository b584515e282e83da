"""comm.node_ms: milliseconds per step in which an on-node collective is in
flight on the busiest device: one whose ``comm.<primitive>[<axes>]`` scope
spans no pod axis (the window gathers of the weights, the reduce-scatters
autodiff makes of them). A union of the intervals ``comm.collective_ms``
counts (``benchmark/scopes.py``); a collective the compiler added without a
scope goes by its replica groups. Nothing to read where the program names
no collective."""

from benchmark.scopes import tier_ms


def read(view):
    return tier_ms(view, "node")
