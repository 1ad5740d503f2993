"""Compiled step: milliseconds per step in which an operation runs on
device 0 (union of the op intervals in the device trace)."""


def read(run):
    s = run["summary"]
    if not s:
        return None
    return 1e3 * s["per_device"][0]["busy_s"] / s["steps"]
