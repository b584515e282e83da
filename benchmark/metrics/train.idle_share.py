"""train.idle_share: the share of the traced window, in %, in which no
operation ran on the busiest device (1 minus the union of its op
intervals over the window)."""


def read(view):
    if view.trace is None:
        return None
    busy = view.trace["devices"][view.trace["busiest"]]["busy_ns"]
    return 100.0 * (1.0 - busy / view.trace["window_ns"])
