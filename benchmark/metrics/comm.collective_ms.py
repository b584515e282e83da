"""comm.collective_ms: milliseconds per step in which a collective
(all-gather, reduce-scatter, all-reduce, collective-permute, all-to-all) is
in flight on the busiest device, found in the device trace by XLA opcode
or, for a fusion that runs one, through the step's compiled module
(``benchmark/trace.py``).  Nothing to read where no collective ran."""


def read(view):
    if view.trace is None:
        return None
    ns = view.trace["devices"][view.trace["busiest"]]["collective_ns"]
    if ns <= 0:
        return None
    return ns / 1e6 / view.raw["steps"]
