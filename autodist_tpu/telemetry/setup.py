"""The program's set-up record: what ``distribute()`` and a session's first
step spend, and what the compiler did inside each part.

Always on, since no profile runs while a job sets up.  A set-up span is
``telemetry.span(name, recorder=RECORDER)``: a profiler annotation like
every ``ad.*`` span, and a ``span`` record (with its ``parent``) in this
module's own small ring, written when the span closes with its counters as
arguments:

- ``trace_s``, ``lower_s``, ``compile_s``: seconds of JAX's compile events
  (``jax.monitoring``'s trace, lowering and backend-compile time spans; a
  persistent-cache hit passes through the backend compile too) that the
  thread running the span reported while this was its innermost open
  set-up span.  Events nest (a ``jnp`` operator on a tracer is a jitted
  call with a trace of its own), so a second counts once, under the kind
  of the innermost event that covers it: per kind the union of its
  intervals, and the kinds together never more than the span lasted.
- ``cache_hits``, ``cache_misses``: the persistent compile cache's events
  (JAX counts a miss where it writes an entry).
- ``state_bytes``, ``step_reserved_bytes``: what the caller counts
  (``Frame.count``).

Events outside every set-up span (a benchmark's own reference, weights)
count nowhere.  After a session's first step nothing here runs: the
listeners return at once where no set-up span is open, and no compile
event comes.  A session hands its totals (:func:`totals`) to every later
``ad.run`` span as arguments (``runner.py``).
"""
import collections
import contextlib
import math
import threading
import time

import jax

from autodist_tpu import telemetry
from autodist_tpu.telemetry.metrics import MetricsRegistry
from autodist_tpu.telemetry.spans import SpanRecorder, open_spans

RECORDER = SpanRecorder(MetricsRegistry(capacity=256))

_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

_lock = threading.Lock()
_listeners = []


class Frame:
    """One set-up span: its own counters, and ``tree``, the sums over it
    and the set-up spans it held (their seconds under their names)."""

    __slots__ = ("name", "parent", "start_ns", "seconds", "counts", "tree",
                 "_events")

    def __init__(self, name, parent):
        self.name = name
        self.parent = parent
        self.start_ns = time.time_ns()
        self.counts = {}
        self.tree = collections.Counter()
        # compile events as disjoint [start_ns, end_ns, {kind: ns}], by end
        self._events = []

    def count(self, **counters):
        for key, value in counters.items():
            self.counts[key] = self.counts.get(key, 0) + value

    def compile_event(self, kind, start_ns, end_ns):
        """An event is reported when it ends, so the ones it holds came
        before it and lie at the tail: they are merged into it and keep
        their seconds; what they leave uncovered is this event's kind's."""
        start_ns = max(start_ns, self.start_ns)
        if end_ns <= start_ns:
            return
        kinds = collections.Counter()
        covered = 0
        while self._events and self._events[-1][1] > start_ns:
            s, e, held = self._events.pop()
            start_ns, end_ns = min(start_ns, s), max(end_ns, e)
            covered += e - s
            kinds.update(held)
        kinds[kind] += max(0, end_ns - start_ns - covered)
        self._events.append((start_ns, end_ns, kinds))

    def close(self):
        seconds = collections.Counter()
        for _, _, kinds in self._events:
            seconds.update(kinds)
        self._events = []
        for kind in _SECONDS.values():
            if seconds[kind]:
                self.counts[kind] = seconds[kind] / 1e9
        self.seconds = (time.time_ns() - self.start_ns) / 1e9
        self.tree.update(self.counts)
        self.tree[self.name] += self.seconds
        if self.parent is not None:
            self.parent.tree.update(self.tree)


def current():
    """This thread's innermost open set-up span, or ``None``."""
    for entry in reversed(open_spans()):
        if isinstance(entry, Frame):
            return entry
    return None


@contextlib.contextmanager
def span(name):
    """``with setup.span("ad.init_state") as frame: ...``"""
    _listen()
    frame = Frame(name, current())
    with telemetry.span(name, recorder=RECORDER, frame=frame) as args:
        try:
            yield frame
        finally:
            frame.close()
            args.update(frame.counts)


def _on_time_span(event, start, end, **_):
    kind = _SECONDS.get(event)
    frame = current() if kind else None
    if frame is not None:
        frame.compile_event(kind, int(start * 1e9), int(end * 1e9))


def _on_event(event, **_):
    counter = _COUNTS.get(event)
    frame = current() if counter else None
    if frame is not None:
        frame.count(**{counter: 1})


def _listen():
    if _listeners:
        return
    with _lock:
        if not _listeners:
            jax.monitoring.register_event_time_span_listener(_on_time_span)
            jax.monitoring.register_event_listener(_on_event)
            _listeners.extend((_on_time_span, _on_event))


def record():
    """The set-up spans recorded in this process, in the order they closed."""
    return RECORDER.events()


def totals(*frames):
    """The arguments a session's ``ad.run`` carries after its first step,
    over its outermost set-up spans ``frames`` (``ad.distribute``, or
    ``ad.init_state`` where the session was built on its own, and
    ``ad.first_run``) and those they held: ``setup_program_s`` is the
    seconds of ``frames`` themselves."""
    tree = collections.Counter()
    for frame in frames:
        tree.update(frame.tree)
    out = {"setup_program_s": sum(frame.seconds for frame in frames),
           "setup_state_s": tree["ad.init_state"],
           "setup_trace_s": tree["trace_s"],
           "setup_lower_s": tree["lower_s"],
           "setup_compile_s": tree["compile_s"],
           "setup_cache_misses": int(tree["cache_misses"]),
           "state_bytes": int(tree["state_bytes"])}
    if "step_reserved_bytes" in tree:
        out["step_reserved_bytes"] = int(tree["step_reserved_bytes"])
    return out


def fullest_device_bytes(tree):
    """Bytes of ``tree``'s arrays on the device that holds most of them:
    each leaf's shard shape times its item size, on each device it is
    addressable from.  Arithmetic on the host; nothing is fetched."""
    per_device = collections.Counter()
    for leaf in jax.tree.leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            continue
        nbytes = math.prod(sharding.shard_shape(leaf.shape)) \
            * leaf.dtype.itemsize
        for device in sharding.addressable_devices:
            per_device[device] += nbytes
    return max(per_device.values(), default=0)
