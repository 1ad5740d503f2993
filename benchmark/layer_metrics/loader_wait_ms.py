"""Data loader: mean milliseconds a step waits for the C++ ring to hand
over an assembled batch (``ad.loader.wait``)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.span_ms(run, "ad.loader.wait")
