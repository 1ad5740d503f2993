"""Compiled step: the part of ``backward_ms`` that is forward work run again
under ``remat``: backward operations whose ``op_name`` holds
``rematted_computation`` (``checkpoint`` alone also sits on true backward
operations).  Nothing where the step recomputes nothing."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms(run, "recompute") or None
