"""Compiled step: milliseconds per step of device-0 self time under no
``ad.`` scope: operations the compiler adds without an ``op_name`` (layout
and donation copies) and the step's few operations outside a stage.  With
the four phases it adds up to ``device_busy_ms``."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms(run, "unscoped")
