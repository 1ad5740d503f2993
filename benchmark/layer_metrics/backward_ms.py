"""Compiled step: milliseconds per step of backward work on device 0, the
operations under ``ad.grad`` whose ``op_name`` holds ``transpose(``,
recomputed forward work included (self time from the device trace)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms(run, "backward")
