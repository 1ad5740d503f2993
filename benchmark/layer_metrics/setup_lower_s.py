"""Set-up: seconds JAX spent lowering to MLIR inside the program's set-up
spans.
Read from the totals the runner writes on every ``ad.run`` after the first
(argument ``setup_lower_s``); nothing where the program writes none."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "setup_lower_s")
    return float(values[0]) if values else None
