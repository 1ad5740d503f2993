"""The measured loop: one step always queued behind the one that runs.

After warm-up the loop dispatches step N and then blocks on step N-1's loss,
so the device always has one step queued and every step's finish gets a host
timestamp.  The three host calls carry ``jax.profiler.TraceAnnotation``s
(free when no trace is being taken) and their host-clock durations are kept.
"""
import math
import time

SPANS = ("bench.input_wait", "bench.dispatch", "bench.block")


class Window:
    """What one window saw.  ``finish`` and ``losses`` are of the steps that
    finished inside it, in order; ``spans`` maps a span name to the seconds
    of each of its calls, one entry per dispatched step."""

    def __init__(self):
        self.dispatched = 0
        self.finish = []
        self.losses = []
        self.spans = {name: [] for name in SPANS}
        self.traced = None              # (first, last) dispatch indices

    @property
    def failed(self):
        return sum(1 for v in self.losses if not math.isfinite(v))


def run_window(next_batch, step, seconds, trace=None):
    """Run the loop for ``seconds``.

    ``next_batch()`` yields the next device batch; ``step(batch)`` dispatches
    one training step and returns its loss (a device scalar) without
    waiting.  ``trace`` is ``None`` or ``(first, last, start, stop)``: the
    profiler is started before dispatch ``first`` and stopped before dispatch
    ``last``, in the running loop, so that the steps between them are traced
    in the steady state (the reduction drops the steps at either edge).
    """
    import jax

    w = Window()

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            out = fn(*args)
        w.spans[name].append(time.perf_counter() - t0)
        return out

    def wait(handle):
        return float(handle.block_until_ready())

    pending = None
    tracing = False
    t_end = time.perf_counter() + seconds
    while True:
        if trace and w.dispatched == trace[0]:
            trace[2]()
            tracing = True
        if tracing and w.dispatched == trace[1]:
            trace[3]()
            tracing = False
            w.traced = (trace[0], trace[1])
        batch = timed("bench.input_wait", next_batch)
        handle = timed("bench.dispatch", step, batch)
        w.dispatched += 1
        if pending is None:
            w.spans["bench.block"].append(0.0)
        else:
            w.losses.append(timed("bench.block", wait, pending))
            w.finish.append(time.perf_counter())
        pending = handle
        if time.perf_counter() >= t_end:
            break
    last_loss = wait(pending)
    if time.perf_counter() <= t_end:    # else it finished outside the window
        w.losses.append(last_loss)
        w.finish.append(time.perf_counter())
    if tracing:
        trace[3]()
        w.traced = (trace[0], w.dispatched)
    return w
