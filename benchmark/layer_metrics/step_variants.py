"""Runner: compiled variants of the training step, the largest ``variants``
on ``ad.run`` over the steady steps.  1 is right; more means a batch of
another shape, dtype or sharding made the step compile again."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "variants")
    return max(values) if values else None
