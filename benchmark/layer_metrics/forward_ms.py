"""Compiled step: milliseconds per step of forward work on device 0, the
operations under the ``ad.grad`` scope whose ``op_name`` holds no
``transpose(`` (self time from the device trace)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms(run, "forward")
