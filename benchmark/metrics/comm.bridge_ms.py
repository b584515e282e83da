"""comm.bridge_ms: milliseconds per step in which a bridge collective is in
flight on the busiest device: one whose ``comm.<primitive>[<axes>]`` scope
spans the pod axis (the gradients' all-reduce across pods). A union of the
intervals ``comm.collective_ms`` counts (``benchmark/scopes.py``); a
collective the compiler added without a scope goes by its replica groups.
Nothing to read where the program names no collective."""

from benchmark.scopes import tier_ms


def read(view):
    return tier_ms(view, "bridge")
