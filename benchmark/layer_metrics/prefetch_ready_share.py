"""Data loader: percentage of hand-overs at which the batch had already
arrived on the device (``ready`` on ``ad.prefetch.next``)."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.prefetch.next", "ready")
    return 100.0 * sum(values) / len(values) if values else None
