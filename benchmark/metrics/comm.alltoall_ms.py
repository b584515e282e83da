"""comm.alltoall_ms: milliseconds per step in which an all-to-all is in
flight on the busiest device (the expert layer's dispatch and combine,
``Communicator.alltoall``, both tiers), a union of intervals
(``benchmark/experts.py:alltoall``). Nothing to read where no all-to-all
ran."""

from benchmark.experts import alltoall


def read(view):
    out = alltoall(view)
    if out is None:
        return None
    return out["inflight_ns"] / 1e6 / view.raw["steps"]
