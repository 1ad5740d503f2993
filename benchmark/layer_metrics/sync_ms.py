"""Gradient sync: milliseconds per step on device 0 under ``ad.materialize``,
``ad.sync`` and ``ad.gather``: collectives, and the packing of gradients
into buckets and slicing them back, which runs on one chip too."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms(run, "sync")
