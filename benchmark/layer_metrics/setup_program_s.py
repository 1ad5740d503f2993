"""Set-up: seconds of the program's own set-up, ``ad.distribute`` (capture,
strategy, transform, the training state) and ``ad.first_run`` (the first
step: trace, lowering, compile or cache load, one step).
Read from the totals the runner writes on every ``ad.run`` after the first
(argument ``setup_program_s``); nothing where the program writes none."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "setup_program_s")
    return float(values[0]) if values else None
