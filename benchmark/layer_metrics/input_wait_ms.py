"""Data loader: mean milliseconds a step's ``next(prefetcher)`` takes, from
the benchmark's own span around it, over the traced steady steps."""


def read(run):
    calls = run["spans"].get("bench.input_wait")
    return 1e3 * sum(calls) / len(calls) if calls else None
