"""comm.alltoall_exposed_ms: the part of ``comm.alltoall_ms`` in which no
operation that computes runs on the busiest device, in milliseconds per
step (``benchmark/experts.py:alltoall``). Nothing to read where no
all-to-all ran."""

from benchmark.experts import alltoall


def read(view):
    out = alltoall(view)
    if out is None:
        return None
    return out["exposed_ns"] / 1e6 / view.raw["steps"]
