"""Data loader: mean milliseconds a step's prefetcher spends placing the
next batch on the device (``ad.shard_batch`` under ``ad.prefetch.push``)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.span_ms(run, "ad.shard_batch",
                                 under="ad.prefetch.push")
