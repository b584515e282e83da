"""comm.exposed_ms: milliseconds per step in which a collective is in
flight on the busiest device and no operation that computes runs there (a
fusion that carries a collective along with a matrix product computes)."""


def read(view):
    if view.trace is None:
        return None
    dev = view.trace["devices"][view.trace["busiest"]]
    if dev["collective_ns"] <= 0:
        return None
    return dev["exposed_ns"] / 1e6 / view.raw["steps"]
