"""Gradient sync: the part of ``collective_ms`` in which no other operation
runs on device 0, so that the step waits for the wire."""


def read(run):
    s = run["summary"]
    if not s:
        return None
    return 1e3 * s["per_device"][0]["collective_exposed_s"] / s["steps"]
