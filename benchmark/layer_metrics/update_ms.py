"""Compiled step: milliseconds per step in gradient clipping and the
optimizer on device 0, the ``ad.clip`` and ``ad.update`` scopes."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms(run, "update")
