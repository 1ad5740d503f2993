"""Device memory: GB the loaded training step reserves for its temporaries
(``bytes_reserved`` right after the first dispatch, the largest over the
session's devices).
Read from the totals the runner writes on every ``ad.run`` after the first
(argument ``step_reserved_bytes``); nothing where the program writes none."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "step_reserved_bytes")
    return values[0] / 1e9 if values else None
