"""Set-up: seconds of ``ad.init_state``, the training state: storage layout,
update space, optimizer init and the copy of host-held weights to the
device.
Read from the totals the runner writes on every ``ad.run`` after the first
(argument ``setup_state_s``); nothing where the program writes none."""
from benchmark.harness import program_trace


def read(run):
    values = program_trace.span_arguments(run, "ad.run", "setup_state_s")
    return float(values[0]) if values else None
