"""Process start to the start of the measured window: imports, loader build,
data written, weights made, the reference, lower, compile or cache hit,
warm-up."""


def read(run):
    return run["setup_s"]
