"""Host-side span records of the telemetry registry.

``SpanRecorder.span`` writes one ``span`` record (name, category,
wall-clock start in microseconds, ``perf_counter`` duration, pid, tid,
arguments) into a registry ring; the JSONL manifest and its schema read
them.  The timeline of a run is not made from these: ``telemetry.span``
also opens a ``jax.profiler.TraceAnnotation``, so a profile holds the
program's ``ad.*`` spans on the device trace's own clock
(docs/observability.md).
"""
import contextlib
import os
import threading
import time


class SpanRecorder:
    """Collects span records into a registry ring."""

    def __init__(self, registry):
        self._registry = registry

    @contextlib.contextmanager
    def span(self, name, cat="host", **args):
        ts_us = time.time_ns() // 1000
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dur_us = (time.perf_counter() - t0) * 1e6
            self._registry.event(
                "span", name=name, cat=cat, ts=ts_us, dur=dur_us,
                pid=os.getpid(), tid=threading.get_ident(),
                **({"args": args} if args else {}))

    def events(self):
        return self._registry.events("span")
