"""The 95th percentile of the milliseconds between two finished steps, over
every step of the window.  Only for cells whose window holds 200 steps and
more, so that ten samples lie beyond it."""
from benchmark.harness.timing import intervals_ms, percentile


def read(run):
    return percentile(intervals_ms(run["window"].finish), 95)
