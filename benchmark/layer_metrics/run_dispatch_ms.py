"""Runner: mean milliseconds a step's call of the jitted program takes to
return (``ad.dispatch``, the ``pjit`` call alone)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.span_ms(run, "ad.dispatch")
