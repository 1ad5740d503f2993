"""Set-up: seconds of backend compiles inside the program's set-up spans; a
hit in the persistent compile cache passes through them too, as a load.
Read from the totals the runner writes on every ``ad.run`` after the first
(argument ``setup_compile_s``); nothing where the program writes none."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "setup_compile_s")
    return float(values[0]) if values else None
