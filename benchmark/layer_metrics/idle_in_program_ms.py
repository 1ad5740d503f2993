"""Device: milliseconds per step in which device 0 runs nothing while the
host is inside one of the program's ``ad.*`` spans: the program's own share
of the blame for an idle chip."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.idle_in_program_ms(run)
