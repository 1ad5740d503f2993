"""Tokens trained per second, summed over the cell's chips, input pipeline
running: whole steps between the first and the last finish in the window."""
from benchmark.harness.timing import throughput


def read(run):
    if run["job"].unit != "tokens":
        return None
    return throughput(run["window"].finish, run["job"].units_per_step)
