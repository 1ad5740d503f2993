"""Runner: mean milliseconds a step's ``sess.run`` takes to return (it
returns before the device is done), from the benchmark's own span around
it, over the traced steady steps."""


def read(run):
    calls = run["spans"].get("bench.dispatch")
    return 1e3 * sum(calls) / len(calls) if calls else None
