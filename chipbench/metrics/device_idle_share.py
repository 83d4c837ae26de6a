"""Percent of the traced window in which no operation ran on the device,
averaged over the cell's devices."""


def read(r):
    if r.trace is None:
        return None
    return (1 - r.trace["busy_s"] / r.trace["window_s"]) * 100
