"""Host-side span records of the telemetry registry.

``SpanRecorder.span`` writes one ``span`` record (name, category,
wall-clock start in microseconds, ``perf_counter`` duration, pid, tid,
``parent``, arguments) into a registry ring; the JSONL manifest and its
schema read them.  ``parent`` is the name of the innermost recorded span
open on the same thread when this one opened, whichever recorder wrote
it.  The timeline of a run is not made from these: ``telemetry.span``
also opens a ``jax.profiler.TraceAnnotation``, so a profile holds the
program's ``ad.*`` spans on the device trace's own clock
(docs/observability.md).
"""
import contextlib
import os
import threading
import time

# the recorded spans open on each thread, innermost last: each span's name,
# or the object with a ``name`` its opener keeps for it (a set-up span's
# ``telemetry.setup.Frame``)
_open = threading.local()


def open_spans():
    """This thread's open recorded spans, innermost last."""
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


class SpanRecorder:
    """Collects span records into a registry ring."""

    def __init__(self, registry):
        self._registry = registry

    @contextlib.contextmanager
    def span(self, name, cat="host", frame=None, **args):
        """Yields the record's arguments: what the caller adds to them
        while the span is open is written with the record.  ``frame`` is
        what :func:`open_spans` holds for the span (its name if ``None``)."""
        stack = open_spans()
        parent = getattr(stack[-1], "name", stack[-1]) if stack else None
        stack.append(name if frame is None else frame)
        ts_us = time.time_ns() // 1000
        t0 = time.perf_counter()
        try:
            yield args
        finally:
            dur_us = (time.perf_counter() - t0) * 1e6
            stack.pop()
            self._registry.event(
                "span", name=name, cat=cat, ts=ts_us, dur=dur_us,
                pid=os.getpid(), tid=threading.get_ident(),
                **({"parent": parent} if parent else {}),
                **({"args": args} if args else {}))

    def events(self):
        return self._registry.events("span")
