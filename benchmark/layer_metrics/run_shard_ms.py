"""Runner: mean milliseconds a step's ``sess.run`` spends in
``_shard_batch`` (``ad.shard_batch`` under ``ad.run``): with a prefetcher
the batch is already placed, and this is what placing it again costs."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.span_ms(run, "ad.shard_batch", under="ad.run")
