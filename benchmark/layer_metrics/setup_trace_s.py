"""Set-up: seconds JAX spent tracing inside the program's set-up spans (the
union of its nested trace events, so a second counts once).
Read from the totals the runner writes on every ``ad.run`` after the first
(argument ``setup_trace_s``); nothing where the program writes none."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "setup_trace_s")
    return float(values[0]) if values else None
