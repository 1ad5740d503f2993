"""Runtime telemetry subsystem (autodist_tpu/telemetry, docs/observability.md).

Covers the acceptance contract end-to-end on the 8-virtual-device CPU
mesh: a 5-step instrumented run emits a schema-valid JSONL manifest with
per-step wall time / throughput / achieved-MFU / memory snapshots,
``tools/telemetry_report.py`` renders it, ``cost_model`` calibrates from
the emitted RuntimeRecord — and the disabled default adds NOTHING to the
hot path (no device sync, no file I/O, no telemetry code).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.autodist import AutoDist
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce

SPEC8 = ResourceSpec.from_num_chips(8)
RS = np.random.RandomState(0)
BATCH = RS.randn(16, 12).astype(np.float32)


def _loss(p, batch):
    return jnp.mean((batch @ p["w"] + p["b"]) ** 2)


def _params():
    r = np.random.RandomState(7)
    return {"w": jnp.asarray(r.randn(12, 3), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}


def _session():
    ad = AutoDist(resource_spec=SPEC8, strategy_builder=AllReduce())
    return ad.distribute(_loss, _params(), optax.sgd(0.1))


@pytest.fixture(autouse=True)
def _clean_telemetry_state():
    """Telemetry enablement is process-global; leave it as found (off)."""
    yield
    telemetry.disable()
    telemetry._STATE["run_dir"] = None
    telemetry.reset_registry()


# -- the 5-step acceptance run ---------------------------------------------

def test_five_step_run_manifest_report_calibrate(tmp_path, monkeypatch):
    from autodist_tpu.utils import timing

    # the table-hit case: the CPU has no entry of its own, and without one
    # a step record carries no mfu (test_step_record_without_peak_entry)
    monkeypatch.setitem(timing.PEAK_BF16_FLOPS, "cpu", 1e12)
    run_dir = str(tmp_path / "run")
    telemetry.enable(run_dir=run_dir)
    sess = _session()
    assert sess._telemetry is not None
    metrics = sess.run_steps([BATCH] * 5, log_every=2)
    assert np.isfinite(float(metrics["loss"]))

    manifest = os.path.join(run_dir, "manifest.jsonl")
    records, errors = telemetry.validate_manifest(manifest, require_steps=True)
    assert errors == []
    steps = [r for r in records if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [0, 1, 2, 3, 4]
    for r in steps:
        assert r["wall_s"] > 0
        assert r["wall_cancelled_s"] >= 0
        assert r["examples"] == 16
        assert r["throughput_eps"] > 0
        assert 0 <= r["mfu"] < 1 and r["peak_flops"] == 1e12
        assert r["flops_per_device"] > 0
        assert r["w"] == 0 and "pid" in r
    snaps = [r for r in records if r["kind"] == "snapshot"]
    assert snaps and all("devices" in r for r in snaps)
    (summary,) = [r for r in records if r["kind"] == "summary"]
    assert summary["steps"] == 5
    assert summary["step_time_p50_s"] > 0
    # measured: the set-up record's trace, lowering and compile seconds
    setup = sess._setup_totals
    assert summary["compile_s"] == pytest.approx(
        setup["setup_trace_s"] + setup["setup_lower_s"]
        + setup["setup_compile_s"])
    assert 0 < summary["compile_s"] < setup["setup_program_s"]
    meta = next(r for r in records if r["kind"] == "meta")
    assert meta["backend"] == "cpu" and meta["num_devices"] == 8
    assert "cost_estimate" in meta  # predicted-vs-measured substrate

    # the report renders the manifest
    from tools.telemetry_report import render, summarize_manifest

    s = summarize_manifest(records)
    text = render(s)
    assert s["steps"] == 5 and s["mfu_p50"] > 0
    assert "p50" in text and "throughput" in text

    # the measured-feedback loop: emitted RuntimeRecord -> calibrate
    from autodist_tpu.simulator.cost_model import (RuntimeRecord,
                                                   calibrate_from_records)

    rec_path = summary["runtime_record"]
    rec = RuntimeRecord.load(rec_path)
    assert rec.backend == "cpu" and rec.step_time_s > 0
    cal, pairs = calibrate_from_records([rec_path])
    assert set(cal) == {"compute_scale", "comm_scale", "overhead_s"}
    assert pairs[0][1] == rec.step_time_s
    assert pairs[0][0].comm_s >= 0  # the rebuilt case priced by estimate()


def test_step_record_without_peak_entry(tmp_path):
    """A device the peak table has no entry for (the CPU here) gets the
    FLOP count and no ``mfu``/``peak_flops``: never an MFU against another
    device's peak."""
    from autodist_tpu.utils.timing import peak_flops

    with pytest.raises(KeyError, match="device_kind"):
        peak_flops()
    telemetry.enable(run_dir=str(tmp_path / "run"))
    sess = _session()
    sess.run_steps([BATCH] * 2)
    records, errors = telemetry.validate_manifest(
        os.path.join(str(tmp_path / "run"), "manifest.jsonl"),
        require_steps=True)
    assert errors == []
    steps = [r for r in records if r["kind"] == "step"]
    assert len(steps) == 2
    for r in steps:
        assert r["flops_per_device"] > 0
        assert "mfu" not in r and "peak_flops" not in r
    (summary,) = [r for r in records if r["kind"] == "summary"]
    assert "mfu_p50" not in summary


def test_disabled_zero_overhead(monkeypatch):
    """Default-off: the hot path must perform no device sync, no file
    I/O, and touch no telemetry code (the acceptance guard).  The first
    step is set-up: the one span it may record is the always-on set-up
    record's.  The steps after it record none."""
    from autodist_tpu.telemetry import setup

    assert not telemetry.enabled()
    sess = _session()
    assert sess._telemetry is None

    def boom(*a, **k):
        raise AssertionError("hot path touched telemetry / sync / file I/O")

    import autodist_tpu.utils.timing as timing

    monkeypatch.setattr(timing, "fetch_scalar", boom)
    monkeypatch.setattr(telemetry.JsonlWriter, "__init__", boom)
    monkeypatch.setattr(telemetry.MetricsRegistry, "counter", boom)
    monkeypatch.setattr(telemetry.MetricsRegistry, "gauge", boom)
    monkeypatch.setattr(jax, "block_until_ready", boom)   # no device sync
    monkeypatch.setattr(jax.profiler, "trace", boom)      # no profiler I/O
    span = telemetry.SpanRecorder.span

    def setup_span_only(recorder, *a, **k):
        if recorder is not setup.RECORDER:
            boom()
        return span(recorder, *a, **k)

    monkeypatch.setattr(telemetry.SpanRecorder, "span", setup_span_only)
    sess.run(BATCH)
    monkeypatch.setattr(telemetry.SpanRecorder, "span", boom)
    for _ in range(3):
        metrics = sess.run(BATCH)
    assert np.isfinite(float(metrics["loss"]))
    # the facade no-ops stay no-ops while disabled
    telemetry.counter("x")
    telemetry.gauge("x", 1)
    with telemetry.span("x"):
        pass


# -- registry / spans / schema / writer ------------------------------------

def test_metrics_registry_aggregates_and_bounds():
    reg = telemetry.MetricsRegistry(capacity=8, hist_capacity=4)
    for i in range(20):
        reg.counter("c", 2.0)
    reg.gauge("g", 7, shard=1)
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        reg.histogram("h", v)
    agg = reg.aggregates()
    assert agg["counters"]["c"] == 40.0
    assert agg["gauges"]["g{shard=1}"] == 7
    # reservoir capped at 4: the first observation fell out
    assert agg["histograms"]["h"]["count"] == 4
    assert agg["histograms"]["h"]["min"] == 2.0
    assert agg["histograms"]["h"]["p50"] in (3.0, 4.0)
    # ring bounded at 8 with eviction accounting
    assert len(reg.events()) == 8
    assert reg.dropped == 26 - 8
    assert reg.counter_value("c") == 40.0
    assert reg.gauge_value("g", shard=1) == 7


def test_registry_export_validates(tmp_path):
    reg = telemetry.MetricsRegistry()
    reg.counter("a")
    reg.gauge("b", 1.5)
    reg.event("step", step=0, wall_s=0.1)
    path = reg.export_jsonl(str(tmp_path / "m.jsonl"),
                            meta={"run_id": "r", "backend": "cpu",
                                  "num_devices": 1})
    records, errors = telemetry.validate_manifest(path)
    assert errors == []
    assert [r["kind"] for r in records] == ["meta", "counter", "gauge", "step"]


def test_schema_validator_catches_bad_records():
    from autodist_tpu.telemetry.schema import validate_lines

    lines = [
        json.dumps({"kind": "step", "step": 0}),          # missing wall_s
        json.dumps({"kind": "step", "step": 1, "wall_s": "fast"}),  # type
        json.dumps({"no_kind": True}),
        "{torn json",
        json.dumps({"kind": "exotic_future_kind", "x": 1}),  # tolerated
    ]
    records, errors = validate_lines(lines)
    assert len(records) == 4
    assert any("wall_s" in e for e in errors)
    assert any("expected number" in e for e in errors)
    assert any("missing 'kind'" in e for e in errors)
    assert any("invalid JSON" in e for e in errors)
    assert not any("exotic" in e for e in errors)


def test_span_recorder_chrome_dump():
    reg = telemetry.MetricsRegistry()
    rec = telemetry.SpanRecorder(reg)
    with rec.span("outer", step=3):
        with rec.span("inner"):
            pass
    events = rec.events()
    assert [e["name"] for e in events] == ["inner", "outer"]  # close order
    assert all(e["dur"] >= 0 and e["ts"] > 0 for e in events)
    assert events[1]["args"] == {"step": 3} and "args" not in events[0]


def test_jsonl_writer_and_merge(tmp_path):
    w0 = telemetry.JsonlWriter(str(tmp_path / "worker_0.jsonl"), worker=0)
    w1 = telemetry.JsonlWriter(str(tmp_path / "worker_1.jsonl"), worker=1)
    w0.write({"kind": "step", "step": 0, "wall_s": 0.1, "t": 10.0})
    w1.write({"kind": "step", "step": 0, "wall_s": 0.2, "t": 5.0})
    w0.write({"kind": "step", "step": 1, "wall_s": 0.1, "t": 20.0})
    w1.write({"kind": "step", "step": 1, "wall_s": 0.2, "t": 15.0})
    w0.close(), w1.close()
    manifest = telemetry.merge_worker_manifests(str(tmp_path))
    records = telemetry.load_manifest(str(tmp_path))
    assert manifest.endswith("manifest.jsonl")
    # clock-offset corrected (worker 1's clock runs 5s behind worker 0's
    # — two shared step indices pin the offset; one alone falls back to
    # 0.0, see estimate_clock_offsets) then time-ordered, rank preserved
    assert [(r["w"], r["t"]) for r in records] == [(0, 10.0), (1, 10.0),
                                                  (0, 20.0), (1, 20.0)]
    # the raw stamp survives for forensics
    w1_rec = next(r for r in records if r["w"] == 1)
    assert w1_rec["t_raw"] == 5.0
    _, errors = telemetry.validate_manifest(manifest)
    assert errors == []
    # empty dir merges to None
    assert telemetry.merge_worker_manifests(str(tmp_path / "nothing")) is None


# -- watchdog ---------------------------------------------------------------

def test_watchdog_trigger_cooldown_budget():
    from autodist_tpu.telemetry.watchdog import SlowStepWatchdog

    wd = SlowStepWatchdog(multiple=3.0, window=8, min_steps=3, cooldown=2,
                          max_captures=1)
    for i in range(5):
        assert not wd.observe(i, 0.1)
    assert not wd.should_capture()
    assert wd.observe(5, 0.5)                 # 5x the rolling median
    assert wd.last_trigger[0] == 5
    assert wd.should_capture()                # consumes the armed flag once
    assert not wd.should_capture()
    assert wd.captures == 1
    assert not wd.observe(6, 9.9)             # cooldown swallows it
    assert not wd.observe(7, 9.9)
    wd.observe(8, 9.9)                        # budget exhausted: no re-arm
    assert not wd.should_capture()


def test_watchdog_auto_capture_in_session(tmp_path):
    from autodist_tpu.telemetry.watchdog import SlowStepWatchdog

    run_dir = str(tmp_path / "run")
    telemetry.enable(run_dir=run_dir)
    sess = _session()
    # hair-trigger watchdog: any step after the first observation is
    # "slow", one capture allowed
    sess._telemetry.watchdog = SlowStepWatchdog(
        multiple=0.0, window=8, min_steps=1, cooldown=0, max_captures=1)
    sess.run_steps([BATCH] * 4)
    records = telemetry.load_manifest(run_dir)
    wd = [r for r in records if r["kind"] == "watchdog"]
    assert len(wd) == 1
    assert os.path.isdir(wd[0]["trace_dir"])
    assert "watchdog" in wd[0]["trace_dir"]
    step_recs = [r for r in records if r["kind"] == "step"]
    assert any(r.get("trace_dir") for r in step_recs)


# -- runner satellites ------------------------------------------------------

def test_run_steps_and_fit_log_without_loss_key():
    """A model whose metrics dict has no "loss" must not crash the
    progress log (defensive scalar logging)."""
    sess = _session()
    from autodist_tpu.runner import DistributedSession

    s = DistributedSession._metrics_log_str({"acc": np.float32(0.5),
                                             "step": np.int32(3),
                                             "vec": np.ones(4)})
    assert "acc=0.5" in s and "step=3" in s and "vec" not in s
    assert "loss=" in DistributedSession._metrics_log_str(
        {"loss": np.float32(1.0), "acc": np.float32(0.5)})
    assert DistributedSession._metrics_log_str({}) == "metrics={}"
    # end-to-end: a session whose run() yields loss-less metrics
    sess.run = lambda b: {"acc": np.float32(0.9)}
    out = sess.run_steps([BATCH] * 2, log_every=1)
    assert float(out["acc"]) == np.float32(0.9)


def test_trace_dir_namespaced_per_step(tmp_path):
    sess = _session()
    m0 = sess.run(BATCH, trace_dir=str(tmp_path))
    m1 = sess.run(BATCH, trace_dir=str(tmp_path))
    assert m0["trace_dir"] == os.path.join(str(tmp_path), "step_0")
    assert m1["trace_dir"] == os.path.join(str(tmp_path), "step_1")
    assert os.path.isdir(m0["trace_dir"]) and os.path.isdir(m1["trace_dir"])
    assert np.isfinite(float(m1["loss"]))


# -- flops / cost model feedback -------------------------------------------

def test_jaxpr_flops_exact_matmul():
    from autodist_tpu.simulator.cost_model import jaxpr_flops

    j = jax.make_jaxpr(lambda a, b: a @ b)(
        jnp.ones((8, 4)), jnp.ones((4, 2)))
    assert jaxpr_flops(j) == 2 * 8 * 4 * 2
    # control flow folds structurally: scan multiplies by trip count
    def scanned(a, b):
        def body(c, _):
            return c @ b, ()
        out, _ = jax.lax.scan(body, a, None, length=5)
        return out

    j2 = jax.make_jaxpr(scanned)(jnp.ones((8, 4)), jnp.ones((4, 4)))
    assert jaxpr_flops(j2) == 5 * 2 * 8 * 4 * 4


def test_traced_step_flops_per_device():
    sess = _session()
    from autodist_tpu.simulator.cost_model import traced_step_flops

    flops = traced_step_flops(sess._t, ((16, 12), "float32"))
    # fwd (B/R,12)@(12,3) + bwd dL/dW (12,B/R)@(B/R,3) on the 8-device
    # mesh: per-device batch is 2 rows -> 2 * (2*2*12*3) = 288
    assert flops == 2 * (2 * 2 * 12 * 3)


def test_calibrate_from_records_rejects_mixed_backends():
    from autodist_tpu.simulator.cost_model import (RuntimeRecord,
                                                   calibrate_from_records)

    recs = [RuntimeRecord(b"", b"", "", 0.1, backend="cpu"),
            RuntimeRecord(b"", b"", "", 0.1, backend="tpu")]
    with pytest.raises(ValueError, match="mixed backends"):
        calibrate_from_records(recs)


# -- cluster heartbeat / async PS metrics ----------------------------------

def test_cluster_monitor_heartbeat_metrics():
    from autodist_tpu.cluster import Cluster

    telemetry.enable()
    reg = telemetry.reset_registry()

    class FakeProc:
        def __init__(self):
            self._polls = 0
            self.returncode = 0

        def poll(self):
            self._polls += 1
            return None if self._polls < 3 else 0

    cl = Cluster(ResourceSpec.from_num_chips(2))
    cl._monitor("worker-a", FakeProc(), poll_s=0.001)
    assert reg.gauge_value("cluster.worker_alive_t", addr="worker-a") > 0
    assert reg.counter_value("cluster.worker_exits", exit_code=0,
                             addr="worker-a") == 1.0


def test_async_ps_first_class_metrics():
    from autodist_tpu.kernel.synchronization.async_ps import AsyncPSSession

    telemetry.enable()
    reg = telemetry.reset_registry()
    params = {"w": jnp.zeros((4,), jnp.float32)}

    def loss(p, b):
        return jnp.sum((p["w"] - b) ** 2)

    s = AsyncPSSession(loss, params, optax.sgd(0.1), staleness=2,
                       num_workers=2)
    batch = np.ones((4,), np.float32)
    s.run([[batch], [batch]], steps=3)
    assert reg.counter_value("async_ps.pushes") == 6.0
    assert reg.gauge_value("async_ps.version") == 6
    assert reg.gauge_value("async_ps.max_lead") >= 0
    assert reg.gauge_value("async_ps.stale_pushes_total") == s.stale_pushes


def test_auto_strategy_note_measured():
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.strategy.auto_strategy import AutoStrategy

    item = ModelItem(_loss, _params(), optax.sgd(0.1))
    b = AutoStrategy(verify=False)
    with pytest.raises(RuntimeError):
        b.note_measured(0.01)
    b.build(item, SPEC8)
    err = b.note_measured(0.01)
    assert np.isfinite(err)
    assert b.last_prediction_error["measured_s"] == 0.01
    assert b.last_prediction_error["strategy"] == b.last_ranking[0][0]
    with pytest.raises(KeyError):
        b.note_measured(0.01, name="NoSuchStrategy")
