"""Data loader: mean milliseconds a step spends copying the batch out of
its ring slot and releasing the slot (``ad.loader.copy``)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.span_ms(run, "ad.loader.copy")
