"""Data loader: mean number of assembled batches waiting in the C++ ring
when the consumer arrives (``ring`` on ``ad.loader.next``): near its
``prefetch + 1`` slots the workers keep up, near 0 the consumer waits."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.loader.next", "ring")
    return sum(values) / len(values) if values else None
