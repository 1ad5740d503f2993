"""Device memory: GB of the training state on the fullest device, from each
array's shard shape and item size (the program's count in set-up).
Read from the totals the runner writes on every ``ad.run`` after the first
(argument ``state_bytes``); nothing where the program writes none."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "state_bytes")
    return values[0] / 1e9 if values else None
