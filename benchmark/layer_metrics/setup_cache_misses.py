"""Set-up: entries the program's set-up wrote to the persistent compile cache
(JAX counts a miss where it writes one); 0 on a warm run.
Read from the totals the runner writes on every ``ad.run`` after the first
(argument ``setup_cache_misses``); nothing where the program writes none."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "setup_cache_misses")
    return float(values[0]) if values else None
