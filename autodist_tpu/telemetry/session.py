"""Per-session runtime instrumentation (the DistributedSession hook).

What one instrumented step records (a ``step`` JSONL line):

- ``wall_s`` — dispatch-to-fetch wall time, made *honest* with the
  discipline of :mod:`autodist_tpu.utils.timing`: the step is closed by
  fetching one device scalar (bytes prove completion), and the
  constant fetch round-trip — measured once by re-fetching the same
  already-materialized scalar — is subtracted out as
  ``wall_cancelled_s`` (the RTT-cancelled per-step figure, clamped at 0).
- ``throughput_eps`` — global examples/second from the batch's leading
  dimension.
- ``mfu`` — achieved model-FLOPs utilization against
  :data:`~autodist_tpu.utils.timing.PEAK_BF16_FLOPS` (absent, with
  ``peak_flops``, on a device the table has no entry for: there is no
  utilization against another device's peak): the numerator is a
  per-device FLOP count of the *traced* step
  (:func:`autodist_tpu.simulator.cost_model.traced_step_flops` — the
  shard_map body jaxpr carries per-device shapes, so the count is
  per-chip work including the backward pass), computed once per session.
- first step carries compile+execute; the compile-vs-execute split is
  estimated at finalize as ``first_wall - median(steady walls)``.

Plus periodic ``snapshot`` records (``memory_stats`` per device, peak
summarized), host ``span`` records, the slow-step watchdog's
``watchdog`` capture events, online ``health_finding`` verdicts from the
:class:`~autodist_tpu.telemetry.health.HealthMonitor` (NaN/Inf loss,
loss/grad-norm spikes, step-time drift — the loss scalar the step
already fetches to close the wall measurement is reused, so health
costs no extra device sync), and a ``summary`` trailer with step-time
percentiles, the health verdict, and the registry aggregates.  At finalize the measured
steady-state median is exported as an AutoSync-style
:class:`~autodist_tpu.simulator.cost_model.RuntimeRecord` so
``cost_model.calibrate()`` can refit from this run
(``docs/observability.md``).
"""
import os
import time

from autodist_tpu.utils import logging


class SessionTelemetry:
    def __init__(self, transformer, *, run_dir=None, run_id=None,
                 registry=None, mem_every=5, watchdog=None, mem_fn=None,
                 worker=None, stream=None):
        from autodist_tpu import telemetry
        from autodist_tpu.const import ENV
        from autodist_tpu.telemetry.metrics import JsonlWriter
        from autodist_tpu.telemetry.spans import SpanRecorder
        from autodist_tpu.telemetry.stream import (StreamPublisher,
                                                   stream_address_from_env)
        from autodist_tpu.telemetry.watchdog import SlowStepWatchdog
        from autodist_tpu.utils.timing import peak_flops

        self._t = transformer
        self.run_id = run_id or getattr(
            getattr(transformer, "strategy", None), "id", None) or \
            time.strftime("%Y%m%d%H%M%S") + f"-{os.getpid()}"
        self.run_dir = run_dir or telemetry.default_run_dir(self.run_id)
        self.worker = int(ENV.AUTODIST_PROCESS_ID.val if worker is None
                          else worker)
        self.registry = registry or telemetry.get_registry()
        self.spans = SpanRecorder(self.registry)
        self._writer = JsonlWriter(
            os.path.join(self.run_dir, f"worker_{self.worker}.jsonl"),
            worker=self.worker)
        # the black box: bounded rings fed on the same step boundary the
        # writer already crosses; dumps are TRIGGERED by failure signals
        # (docs/observability.md "Postmortem tier").  A session only
        # exists when telemetry is on, so this never costs a disabled run.
        from autodist_tpu.telemetry.flight_recorder import recorder

        self.flight = recorder(worker=self.worker, run_dir=self.run_dir)
        # live control plane (docs/observability.md): push compact frames
        # to the chief's collector when one is configured.  Best-effort
        # only — a dead collector degrades to the file-only path above.
        self.stream = None
        stream_addr = stream if stream is not None \
            else stream_address_from_env()
        if isinstance(stream_addr, StreamPublisher):
            self.stream = stream_addr
        elif stream_addr:
            try:
                self.stream = StreamPublisher(
                    stream_addr, worker=self.worker,
                    addr=ENV.AUTODIST_WORKER.val or None)
            except (ValueError, OSError) as e:
                logging.warning("telemetry: bad stream address %r (%s); "
                                "falling back to file-only telemetry",
                                stream_addr, e)
        self._mem_every = max(1, int(mem_every))
        self._mem_fn = mem_fn
        if watchdog is None:
            wd_env = os.environ.get("AUTODIST_TELEMETRY_WATCHDOG", "1")
            watchdog = None if wd_env in ("0", "False") else SlowStepWatchdog(
                multiple=float(os.environ.get(
                    "AUTODIST_TELEMETRY_WATCHDOG_MULT", "3.0")))
        self.watchdog = watchdog or None
        if os.environ.get("AUTODIST_TELEMETRY_HEALTH", "1") in \
                ("0", "False"):
            self.health = None
        else:
            from autodist_tpu.telemetry.health import HealthMonitor

            self.health = HealthMonitor()
        self._n = 0                    # instrumented steps completed
        self._t0 = None
        self._rtt_s = None
        self._first_wall = None
        self._walls = []               # steady-state RTT-cancelled walls
        self._mfus = []
        try:
            self._peak_flops = peak_flops()
        except KeyError:               # device not in PEAK_BF16_FLOPS
            self._peak_flops = None    # step records carry no mfu
        self._flops_per_device = None  # lazy; None = not yet / failed
        self._flops_failed = False
        self._est = None               # CostEstimate (runtime-audit input)
        self.finalized = False
        self._write_meta()

    # -- plumbing ----------------------------------------------------------

    def _write_meta(self):
        import jax

        from autodist_tpu.telemetry.schema import SCHEMA_VERSION

        devices = list(self._t.mesh.devices.flat)
        meta = {
            "kind": "meta", "t": time.time(), "run_id": self.run_id,
            "schema": SCHEMA_VERSION,
            "backend": jax.default_backend(),
            "num_devices": len(devices),
            "device_kind": getattr(devices[0], "device_kind", "?"),
            "sync_schedule": getattr(self._t, "sync_schedule", None),
            "run_dir": self.run_dir,
        }
        # chosen sync hierarchy + static per-hop wire volumes, so reports
        # can put predicted per-hop comm time next to measured walls
        try:
            hier = self._t.hierarchy_summary()
        except Exception:
            hier = None
        if hier is not None:
            meta["hierarchy"] = hier
            if hier["mode"] == "two_level":
                self.registry.gauge("sync.ici_hop_bytes",
                                    hier["ici_hop_bytes"])
                self.registry.gauge("sync.dcn_hop_bytes",
                                    hier["dcn_hop_bytes"])
                for g in ("ici_hop_bytes", "dcn_hop_bytes"):
                    self._publish({"kind": "gauge", "name": f"sync.{g}",
                                   "value": hier[g]})
        # ZeRO sharded weight update: whether the session runs it, plus
        # the per-chip shard volume and the fresh-param gather bytes that
        # replaced the gradient all-gather (docs/performance.md "Sharded
        # weight update")
        try:
            shup = self._t.sharded_update_summary()
        except Exception:
            shup = None
        if shup is not None:
            meta["sharded_update"] = shup
            self.registry.gauge("sync.sharded_update",
                                1.0 if shup["enabled"] else 0.0)
            if shup["enabled"]:
                self.registry.gauge("sync.shard_bytes",
                                    shup["shard_bytes"])
                self.registry.gauge("sync.param_gather_bytes",
                                    shup["param_gather_bytes"])
        est = self._predicted_estimate()
        if est is not None:
            meta["cost_estimate"] = est
        self._writer.write(meta)

    def _predicted_estimate(self):
        """Analytic cost-model prediction for this session's strategy on a
        same-size single-node spec — recorded so the report can show
        predicted-vs-measured and the overlap credit next to real walls."""
        try:
            from autodist_tpu.resource_spec import ResourceSpec
            from autodist_tpu.simulator.cost_model import estimate

            R = len(list(self._t.mesh.devices.flat))
            est = estimate(self._t.strategy, self._t.model_item,
                           ResourceSpec.from_num_chips(R))
            self._est = est     # the runtime audit prices captures with it
            return est.to_json()
        except Exception:
            return None

    def span(self, name, **args):
        from autodist_tpu import telemetry

        return telemetry.span(name, recorder=self.spans, **args)

    def _publish(self, frame):
        """Push one frame to the live collector (non-blocking no-op when
        streaming is off or the collector died)."""
        if self.stream is not None:
            self.stream.publish(frame)

    # -- per-step hooks (called by DistributedSession.run) -----------------

    def step_started(self):
        self._t0 = time.perf_counter()

    def arm_capture_dir(self):
        """Watchdog-armed one-step profiler dir for the upcoming step, or
        None.  Consumes the armed flag."""
        if self.watchdog is None or not self.watchdog.should_capture():
            return None
        path = os.path.join(self.run_dir, "watchdog", f"step_{self._n}")
        # arm-reason + capture path enter the flight ring NOW — a crash
        # mid-capture must still leave the trigger in the bundle (the
        # post-capture analyzer may never run)
        self.flight.note_watchdog(self.watchdog.last_arm_reason, path)
        return path

    def _sync_metrics(self, metrics):
        """Close the step at a REAL synchronization point: fetch one device
        scalar (prefer the loss).  Returns the fetched scalar (the health
        monitor judges it — no second sync) or None; the RTT estimate is
        measured once by re-fetching the already-materialized scalar."""
        from autodist_tpu.utils.timing import fetch_scalar

        leaf = None
        if isinstance(metrics, dict) and "loss" in metrics:
            leaf = metrics["loss"]
        else:
            import jax

            for x in jax.tree.leaves(metrics):
                leaf = x
                break
        if leaf is None:
            return None
        try:
            val = fetch_scalar(leaf)
            if self._rtt_s is None:
                t0 = time.perf_counter()
                fetch_scalar(leaf)
                self._rtt_s = time.perf_counter() - t0
            return val
        except Exception:
            return None

    def _ensure_flops(self, gbatch):
        if self._flops_per_device is not None or self._flops_failed:
            return self._flops_per_device
        try:
            import jax

            from autodist_tpu.simulator.cost_model import traced_step_flops

            batch_shapes = jax.tree.map(
                lambda x: (tuple(x.shape), str(x.dtype)), gbatch)
            self._flops_per_device = traced_step_flops(self._t, batch_shapes)
        except Exception as e:
            self._flops_failed = True
            logging.debug("telemetry: traced FLOP count unavailable (%s)", e)
        return self._flops_per_device

    @staticmethod
    def _batch_examples(gbatch):
        import jax

        for x in jax.tree.leaves(gbatch):
            if getattr(x, "ndim", 0) >= 1:
                return int(x.shape[0])
        return None

    def step_finished(self, metrics, gbatch=None, trace_dir=None,
                      watchdog_capture=False):
        """Record one completed step; returns the step record dict."""
        loss_val = self._sync_metrics(metrics)
        wall = time.perf_counter() - self._t0 if self._t0 is not None else 0.0
        self._t0 = None
        step = self._n
        self._n += 1
        rtt = self._rtt_s or 0.0
        cancelled = max(0.0, wall - rtt)
        eff = cancelled if cancelled > 0 else wall
        rec = {"kind": "step", "t": time.time(), "step": step,
               "wall_s": wall, "wall_cancelled_s": cancelled}
        examples = self._batch_examples(gbatch) if gbatch is not None else None
        if examples:
            rec["examples"] = examples
            if eff > 0:
                rec["throughput_eps"] = examples / eff
        flops = self._ensure_flops(gbatch) if gbatch is not None else None
        if flops and eff > 0:
            rec["flops_per_device"] = flops
            if self._peak_flops:
                mfu = flops / (eff * self._peak_flops)
                rec["mfu"] = mfu
                rec["peak_flops"] = self._peak_flops
                self._mfus.append(mfu)
        if trace_dir:
            rec["trace_dir"] = trace_dir
        if step == 0:
            self._first_wall = cancelled
        else:
            self._walls.append(cancelled)
        self._writer.write(rec)
        self.flight.note_step(rec)
        frame = {"kind": "step", "step": step, "wall_s": eff}
        if loss_val is not None:
            try:
                frame["loss"] = float(loss_val)
            except (TypeError, ValueError):
                pass
        self._publish(frame)
        self.registry.histogram("session.step_wall_s", wall)
        # a loss's auxiliary scalars (``has_aux``; e.g. the routing counters
        # ``moe_rows_here`` ...): the step is synced already, so reading
        # them waits for nothing
        for name, value in (metrics.items() if isinstance(metrics, dict)
                            else ()):
            if name not in ("loss", "step", "grad_norm", "trace_dir") \
                    and getattr(value, "ndim", None) == 0:
                self.registry.gauge("step." + name, float(value))
        if self.health is not None:
            grad_norm = None
            if isinstance(metrics, dict) and "grad_norm" in metrics:
                try:
                    from autodist_tpu.utils.timing import fetch_scalar

                    grad_norm = fetch_scalar(metrics["grad_norm"])
                except Exception:
                    grad_norm = None
            health_findings = self.health.observe(
                step, loss=loss_val, grad_norm=grad_norm, wall_s=eff)
            for hf in health_findings:
                self._writer.write({"kind": "health_finding",
                                    "t": time.time(), **hf})
                self.flight.note_finding(
                    {"kind": "health_finding", "t": time.time(), **hf})
                self._publish({"kind": "health_finding", **hf})
                self.registry.counter(f"health.{hf['check']}")
                logging.warning("telemetry health: %s", hf["message"])
            if health_findings:
                # the returned record carries the verdicts so the caller
                # (ElasticTrainer.on_anomaly) can react without re-deriving
                rec["health_findings"] = health_findings
        if self.watchdog is not None and not watchdog_capture:
            if self.watchdog.observe(step, wall):
                s, w, med = self.watchdog.last_trigger
                logging.warning(
                    "telemetry watchdog: step %d took %.3fs (> %.1fx rolling "
                    "median %.3fs); arming one-step profiler capture.",
                    s, w, self.watchdog.multiple, med)
                # record WHY the capture armed into the metrics stream —
                # a manifest reader can audit the trigger (median, wall,
                # multiple), not just see that one happened
                reason = self.watchdog.last_arm_reason
                if reason is not None and reason.get("step") == s:
                    self._writer.write({"kind": "watchdog_armed",
                                        "t": time.time(), **reason})
                    self.registry.counter("session.watchdog_armed")
        if watchdog_capture and trace_dir:
            self._writer.write({"kind": "watchdog", "t": time.time(),
                                "step": step, "trace_dir": trace_dir})
            self.registry.counter("session.watchdog_captures")
            self._analyze_capture(step, trace_dir)
            if self.watchdog is not None:
                self.watchdog.capture_finished()
            self.flight.capture_done()
        if step == 0 or (step + 1) % self._mem_every == 0:
            self._memory_snapshot(step)
            self._publish({"kind": "heartbeat", "step": step})
        return rec

    def _analyze_capture(self, step, trace_dir):
        """Auto-run the runtime (measured-tier) analyzer over a watchdog
        capture: T-code findings land in the metrics stream as
        ``runtime_finding`` records + ``runtime_audit.<code>`` counters,
        and measured per-hop bandwidths become ``sync.measured_*_bw``
        gauges.  Best-effort — analysis must never break training."""
        try:
            from autodist_tpu.analysis.runtime_audit import runtime_audit
            from autodist_tpu.telemetry import timeline

            tsummary = timeline.summarize_trace(trace_dir)
            if tsummary is None:
                return
            try:
                plan = self._t.intended_collectives()
            except Exception:
                plan = None
            findings = runtime_audit(tsummary, plan, self._est,
                                     source=f"watchdog step {step}")
            for f in findings:
                self.registry.counter(f"runtime_audit.{f.code}")
                rec = {"kind": "runtime_finding", "t": time.time(),
                       "step": step, "code": f.code,
                       "severity": str(f.severity), "message": f.message}
                self._publish({"kind": "runtime_finding", "step": step,
                               "code": f.code,
                               "severity": str(f.severity)})
                if f.code == "T006" and f.data:
                    rec["data"] = f.data
                    for hop, key in (("ici", "sync.measured_ici_bw"),
                                     ("dcn", "sync.measured_dcn_bw")):
                        bw = f.data["measured_bandwidths"].get(
                            f"{hop}_gbps")
                        if bw:
                            self.registry.gauge(key, bw)
                self._writer.write(rec)
        except Exception as e:
            logging.debug("telemetry: runtime audit of capture failed (%s)",
                          e)

    def _memory_snapshot(self, step):
        if self._mem_fn is None:
            return
        try:
            stats = self._mem_fn()
        except Exception:
            return
        peak = None
        for s in (stats or {}).values():
            if isinstance(s, dict):
                p = s.get("peak_bytes_in_use", s.get("bytes_in_use"))
                if p is not None:
                    peak = max(peak or 0, int(p))
        rec = {"kind": "snapshot", "t": time.time(), "step": step,
               "devices": stats}
        if peak is not None:
            rec["peak_bytes"] = peak
            self.registry.gauge("session.hbm_peak_bytes", peak)
            self.flight.note_gauge("session.hbm_peak_bytes", peak,
                                   step=step)
        self._writer.write(rec)

    # -- run trailer -------------------------------------------------------

    def finalize(self):
        """Write the summary trailer, dump the measured RuntimeRecord,
        and (on the chief) merge worker manifests.
        Idempotent — safe to call after every run_steps/fit."""
        from autodist_tpu.telemetry.aggregate import merge_worker_manifests
        from autodist_tpu.telemetry.metrics import percentiles

        if self._n == 0:
            return None
        walls = self._walls or (
            [self._first_wall] if self._first_wall is not None else [])
        ps = percentiles(walls)
        summary = {"kind": "summary", "t": time.time(), "steps": self._n,
                   "step_time_p50_s": ps[0.5], "step_time_p90_s": ps[0.9],
                   "step_time_p99_s": ps[0.99]}
        if self._rtt_s is not None:
            summary["rtt_s"] = self._rtt_s
        if self._walls and self._first_wall is not None:
            summary["compile_s"] = max(0.0, self._first_wall - ps[0.5])
        if self._mfus:
            summary["mfu_p50"] = percentiles(self._mfus)[0.5]
        rec_path = self._dump_runtime_record(ps[0.5])
        if rec_path:
            summary["runtime_record"] = rec_path
        # chief: cross-worker step skew from the (clock-offset corrected)
        # worker files, BEFORE the summary so the gauge lands in its
        # aggregates; a persistent straggler here is the T002 signal
        # ElasticTrainer.note_straggler consumes
        if self.worker == 0:
            try:
                from autodist_tpu.telemetry import timeline
                from autodist_tpu.telemetry.aggregate import merge_records

                sk = timeline.step_skew(merge_records(self.run_dir)[0])
                if sk is not None:
                    self.registry.gauge("cluster.step_skew_s", sk["skew_s"])
                    summary["step_skew"] = sk
            except Exception:
                pass
        if self.health is not None:
            summary["health"] = self.health.summary()
        if self.stream is not None:
            st = self.stream.stats()
            summary["stream"] = st
            self.registry.gauge("stream.sent", st["sent"])
            self.registry.gauge("stream.dropped", st["dropped"])
            self.stream.close()
        summary["aggregates"] = self.registry.aggregates()
        self._writer.write(summary)
        manifest = None
        if self.worker == 0:
            manifest = merge_worker_manifests(self.run_dir)
        self.finalized = True
        logging.info("telemetry: run %s — %d steps, p50 %.4fs (manifest: %s)",
                     self.run_id, self._n, ps[0.5] or 0.0,
                     manifest or self._writer.path)
        return manifest or self._writer.path

    def _dump_runtime_record(self, step_time_s):
        """Measured-feedback loop: export this run as an AutoSync-style
        RuntimeRecord that ``cost_model.calibrate_from_records`` refits
        from (CPU-backend records stay pipeline artifacts, never hardware
        claims — the backend label travels with the record)."""
        if not step_time_s or step_time_s <= 0:
            return None
        try:
            import jax

            from autodist_tpu.simulator.cost_model import RuntimeRecord

            rec = RuntimeRecord(
                model_def=self._t.model_item.serialize(),
                strategy_pb=self._t.strategy.proto.SerializeToString(),
                resource_yaml="",
                step_time_s=float(step_time_s),
                backend=jax.default_backend())
            return rec.dump(os.path.join(
                self.run_dir, f"runtime_record_worker_{self.worker}.json"))
        except Exception as e:
            logging.debug("telemetry: RuntimeRecord export failed (%s)", e)
            return None
