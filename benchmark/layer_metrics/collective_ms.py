"""Gradient sync: milliseconds per step in all-reduce, reduce-scatter and
all-gather operations on device 0 (union of their intervals)."""


def read(run):
    s = run["summary"]
    if not s:
        return None
    return 1e3 * s["per_device"][0]["collective_s"] / s["steps"]
