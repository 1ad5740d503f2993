"""Runtime telemetry: per-step metrics, span tracing, worker aggregation.

The observability layer of the stack (``docs/observability.md``):

- :mod:`~autodist_tpu.telemetry.metrics` — zero-dep counters / gauges /
  histograms in a bounded ring, JSONL export per host;
- :mod:`~autodist_tpu.telemetry.spans` — ``telemetry.span("name")``:
  a ``jax.profiler.TraceAnnotation`` always (the ``ad.*`` host spans of a
  profile, on the device trace's clock) and, when telemetry is on, a
  registry ``span`` record too;
- :mod:`~autodist_tpu.telemetry.session` — per-step session
  instrumentation (wall time, throughput, achieved MFU, memory
  snapshots, compile split) for :class:`DistributedSession`;
- :mod:`~autodist_tpu.telemetry.watchdog` — slow-step auto-capture;
- :mod:`~autodist_tpu.telemetry.health` — online NaN/Inf, loss-spike,
  grad-norm and step-time-drift detectors (``health_finding`` records,
  the ``ElasticTrainer.on_anomaly`` signal);
- :mod:`~autodist_tpu.telemetry.baseline` — committed cross-run perf
  baselines (``records/baselines``, the regression audit's memory);
- :mod:`~autodist_tpu.telemetry.aggregate` — chief-side merge of
  per-worker manifests;
- :mod:`~autodist_tpu.telemetry.stream` — the LIVE control plane
  (``make monitor-check``): worker->chief metric frames over a
  length-prefixed-JSON socket, the chief's :class:`ClusterView`;
- :mod:`~autodist_tpu.telemetry.events` — the causal cluster event log
  (schema v3 ``cluster_event`` records: signals, actions, cause,
  signal->action latency — the E-code reaction audit's input);
- :mod:`~autodist_tpu.telemetry.flight_recorder` — the per-worker black
  box: bounded in-memory rings, anomaly-TRIGGERED
  ``postmortem/<trigger>_<step>/`` bundle dumps, chief-side
  cluster-causal assembly (the P-code postmortem audit's input);
- :mod:`~autodist_tpu.telemetry.schema` — the JSONL schema + validator
  (``make telemetry-check``).

**Off by default.**  Enable per process with ``AUTODIST_TELEMETRY=1``
(workers launched by the chief inherit it through the worker-env
contract) or per session with ``telemetry.enable(run_dir=...)``.  When
disabled, the facade functions below are constant-time no-ops and
``DistributedSession.run`` takes its uninstrumented hot path — no
device sync, no file I/O (pinned by
``tests/test_telemetry.py::test_disabled_zero_overhead``).
"""
import contextlib
import os
import time

from autodist_tpu.telemetry.aggregate import (load_manifest,
                                              load_manifest_with_stats,
                                              merge_worker_manifests)
from autodist_tpu.telemetry.events import ClusterEventLog, load_events
from autodist_tpu.telemetry.flight_recorder import FlightRecorder
from autodist_tpu.telemetry.health import HealthMonitor
from autodist_tpu.telemetry.metrics import (JsonlWriter, MetricsRegistry,
                                            percentiles)
from autodist_tpu.telemetry.schema import validate_manifest
from autodist_tpu.telemetry.spans import SpanRecorder
from autodist_tpu.telemetry.stream import (ClusterView, StreamPublisher,
                                           TelemetryCollector,
                                           stream_address_from_env)
from autodist_tpu.telemetry.watchdog import SlowStepWatchdog

__all__ = [
    "enabled", "enable", "disable", "get_registry", "reset_registry",
    "counter", "gauge", "histogram", "span", "default_run_dir",
    "MetricsRegistry", "JsonlWriter", "SpanRecorder", "SlowStepWatchdog",
    "SessionTelemetry", "percentiles",
    "validate_manifest", "merge_worker_manifests", "load_manifest",
    "load_manifest_with_stats", "HealthMonitor",
    "ClusterView", "StreamPublisher", "TelemetryCollector",
    "stream_address_from_env", "ClusterEventLog", "load_events",
    "FlightRecorder", "flight",
]

_STATE = {
    "enabled": os.environ.get("AUTODIST_TELEMETRY", "") in ("1", "True"),
    "run_dir": os.environ.get("AUTODIST_TELEMETRY_DIR", "") or None,
    "registry": None,
}


def enabled():
    return _STATE["enabled"]


def enable(run_dir=None):
    """Turn telemetry on for this process (sessions built afterwards are
    instrumented; facade counters/gauges/spans start recording)."""
    _STATE["enabled"] = True
    if run_dir:
        _STATE["run_dir"] = os.path.abspath(run_dir)


def disable():
    _STATE["enabled"] = False


def configured_run_dir():
    return _STATE["run_dir"]


def default_run_dir(run_id):
    """Run directory for a run id: the configured dir (env/enable()) or
    ``DEFAULT_TRACE_DIR/telemetry/<run_id>``."""
    if _STATE["run_dir"]:
        return _STATE["run_dir"]
    from autodist_tpu.const import DEFAULT_TRACE_DIR

    return os.path.join(DEFAULT_TRACE_DIR, "telemetry", str(run_id))


def get_registry():
    """The process-global registry (created on first use)."""
    reg = _STATE["registry"]
    if reg is None:
        reg = _STATE["registry"] = MetricsRegistry()
    return reg


def reset_registry():
    """Fresh process-global registry (test isolation)."""
    _STATE["registry"] = MetricsRegistry()
    return _STATE["registry"]


# -- cheap facade: constant-time no-ops when disabled -----------------------

def counter(name, value=1.0, **labels):
    if _STATE["enabled"]:
        get_registry().counter(name, value, **labels)


def gauge(name, value, **labels):
    if _STATE["enabled"]:
        get_registry().gauge(name, value, **labels)


def histogram(name, value, **labels):
    if _STATE["enabled"]:
        get_registry().histogram(name, value, **labels)


def flight(worker=None, run_dir=None):
    """The process's flight recorder (black box), or ``None`` when
    telemetry is disabled — the zero-overhead gate: a disabled process
    never constructs a recorder, so the hot path performs no ring work
    at all (pinned by ``tests/test_flight_recorder.py``)."""
    if not _STATE["enabled"]:
        return None
    from autodist_tpu.telemetry.flight_recorder import recorder

    return recorder(worker=worker, run_dir=run_dir)


def span(name, recorder=None, **args):
    """``with telemetry.span("ad.shard_batch", batch=3):`` — the one span
    entry of the program.  Always a ``jax.profiler.TraceAnnotation``: inert
    while no profile is taken, and in a profile a host span on the device
    trace's clock with ``args`` as its stats.  When telemetry is enabled
    (or a session hands in its own ``recorder``) the registry ``span``
    record is written as well."""
    import jax

    annotation = jax.profiler.TraceAnnotation(name, **args)
    if recorder is None:
        if not _STATE["enabled"]:
            return annotation
        recorder = SpanRecorder(get_registry())
    return _recorded(annotation, recorder.span(name, **args))


@contextlib.contextmanager
def _recorded(annotation, record):
    with annotation, record:
        yield


def new_run_id():
    return time.strftime("%Y%m%d%H%M%S") + f"-{os.getpid()}"


def __getattr__(name):
    # SessionTelemetry pulls in jax-adjacent imports; load lazily so the
    # facade stays import-light for processes that never instrument
    if name == "SessionTelemetry":
        from autodist_tpu.telemetry.session import SessionTelemetry

        return SessionTelemetry
    raise AttributeError(name)
