"""DistributedSession: the steady-state runtime.

Reference ``autodist/runner.py`` (WrappedSession) + ``remapper.py``: the
session remaps user feeds into per-replica placeholders (np.array_split on
the polymorphic batch dim) and contracts fetches back to the master replica.
TPU equivalent: a global batch array is sharded over the replica mesh axis
(`jax.device_put` with a NamedSharding; on multi-host,
``host_local_array_to_global_array``), the jitted SPMD step runs, and
metrics come back replicated (fetch contraction = reading any shard).
"""
import collections
import contextlib
import os
import signal
import threading

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu import telemetry as _telemetry
from autodist_tpu.const import BATCH_MASK_KEY
from autodist_tpu.kernel.partitioner import Placement
from autodist_tpu.utils import logging


class PreemptionGuard:
    """SIGTERM/SIGINT drain hook for training loops (docs/elasticity.md).

    A preemption notice must not kill the process mid-step: the guard
    turns the signal into a flag the loop checks at the next step
    boundary, where it drains (the in-flight step completes), writes a
    manifest checkpoint, and returns cleanly — the TPU-pod / spot-VM
    preemption contract.  Previous handlers are restored on exit.  Off
    the main thread (where CPython forbids ``signal.signal``) the guard
    degrades to an inert flag holder rather than failing the loop.
    """

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self._signals = signals
        self._prev = {}
        self._received = None

    def __enter__(self):
        if threading.current_thread() is threading.main_thread():
            for s in self._signals:
                self._prev[s] = signal.signal(s, self._on_signal)
        return self

    def _on_signal(self, signum, frame):
        logging.warning(
            "Received signal %d: draining the in-flight step, then "
            "writing a preemption checkpoint", signum)
        self._received = signum

    @property
    def requested(self):
        return self._received is not None

    def __exit__(self, *exc):
        for s, h in self._prev.items():
            signal.signal(s, h)
        self._prev = {}
        return False


class DistributedSession:
    def __init__(self, transformer, rng=None, donate=True, batch_mask=False,
                 verify=False, hbm_bytes_per_device=None, telemetry=None):
        self._t = transformer
        self._mesh = transformer.mesh
        self._axis = transformer.axis
        self.state = transformer.init_state(rng=rng)
        if transformer.sync_schedule == "overlap":
            # the step compiles with the latency-hiding scheduler + bucket-
            # sized combine thresholds on TPU (kernel/xla_options.py, via
            # make_train_step); log what this backend actually gets so an
            # overlap run's compile configuration is auditable
            from autodist_tpu.kernel.xla_options import compiler_options_for

            opts = compiler_options_for("overlap")
            logging.info(
                "Overlap sync schedule on %s backend: compiler options %s",
                jax.default_backend(),
                opts or "none (TPU-only flags skipped)")
        self._step = transformer.make_train_step(donate=donate)
        self._batch_spec = transformer.batch_spec
        self._multi_host = jax.process_count() > 1
        self._eval_cache = {}
        # uneven-batch pad+mask is OPT-IN (distribute(batch_mask=True)):
        # the loss must exclude masked rows from its local mean, otherwise
        # pad rows silently bias the update — a loud error beats that
        self._batch_mask = batch_mask
        self._warned_uneven = False
        self._dumped_artifacts = False
        # opt-in static verification (docs/analysis.md): the first run()
        # re-traces the step abstractly — batch shapes are only known then
        # — and raises StrategyVerificationError on ERROR-level findings
        # BEFORE the step executes (a deadlocking collective would hang a
        # pod, not raise)
        self._verify = verify
        self._verify_budget = hbm_bytes_per_device
        self._donate = donate
        self._verified = False
        # set True when a run_steps/fit loop exited via the preemption
        # hook (docs/elasticity.md) after writing its manifest checkpoint
        self.preempted = False
        # runtime telemetry (autodist_tpu/telemetry, docs/observability.md):
        # OFF by default — ``run`` then takes the uninstrumented hot path
        # (no device sync, no file I/O; pinned by test_telemetry).  Opt in
        # per process (AUTODIST_TELEMETRY=1 / telemetry.enable()) or per
        # session (telemetry=True or a prebuilt SessionTelemetry).
        if telemetry is None:
            telemetry = _telemetry.enabled()
        if telemetry is True:
            from autodist_tpu.telemetry.session import SessionTelemetry

            self._telemetry = SessionTelemetry(
                transformer, mem_fn=self.memory_stats)
        else:
            self._telemetry = telemetry or None
        # the program's host spans (``ad.*``, PERF.md section 3): always a
        # profiler annotation, and the session's registry record besides
        # when it is instrumented
        self._span = (self._telemetry.span if self._telemetry is not None
                      else _telemetry.span)
        # calls of ``run`` so far (``step_num`` of ``ad.run``; ``self.step``
        # would fetch from the device) and compiled variants of the step
        self._dispatches = 0
        self._variants = 0
        # a loss with auxiliary outputs (``has_aux``): the newest dispatches'
        # auxiliary metrics, until one has finished and rides on ``ad.run``
        self._aux_pending = collections.deque(maxlen=4)

    # -- feeds (reference remapper._remap_feed analog) ---------------------

    def _spec_dim_size(self, entry):
        """Mesh-device count a batch dim is split across for one spec entry."""
        if entry is None:
            return 1
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= self._mesh.shape[a]
        return size

    def _pad_uneven(self, batch):
        """Uneven global batch -> (padded batch + validity mask, n_pad).

        The reference's remapper np.array_splits a polymorphic batch so every
        example is used exactly once and the synchronized update equals the
        *weighted* average of per-replica gradients (``remapper.py:109-118``,
        asserted by ``cases/c0.py:88-121``).  SPMD requires equal shard
        shapes, so instead: pad dim 0 up to the next multiple of the replica
        count by repeating the last example, and inject a ``BATCH_MASK_KEY``
        leaf (1.0 real / 0.0 pad).  The engine scales each device's loss by
        ``s_local * R / S`` so every sync path reproduces the reference's
        weighted average.  REQUIRES a mask-aware loss (one that excludes
        masked rows from its local mean — all ``models.train_lib`` losses
        are); that is why the session must opt in via
        ``distribute(batch_mask=True)``.  Only dict batches can carry the
        mask leaf.
        """
        B = self._maskable_batch_size(batch)
        if B is None:
            return batch, 0
        # pad to a multiple of replicas x accum_steps so the microbatch
        # split inside the engine divides evenly too
        n0 = self._spec_dim_size(tuple(self._batch_spec)[0]) * self._t.accum_steps
        pad = (-B) % n0
        if pad == 0:
            return batch, 0
        if not self._warned_uneven:
            self._warned_uneven = True
            logging.warning(
                "Global batch %d not divisible by replica count %d: padding "
                "%d row(s) + '%s' mask (loss must ignore masked rows; "
                "warning logged once).", B, n0, pad, BATCH_MASK_KEY)
        return self._pad_to(batch, B, B + pad), pad

    def _maskable_batch_size(self, batch):
        """Leading batch size if this batch is eligible for pad+mask (dict,
        single leading dim, no mask yet), else None."""
        spec = tuple(self._batch_spec)
        if not spec or not isinstance(batch, dict) or BATCH_MASK_KEY in batch:
            return None
        sizes = {np.shape(v)[0] for v in jax.tree.leaves(batch)
                 if np.ndim(v) >= 1}
        if len(sizes) != 1:
            return None  # mixed leading dims: let divisibility checks fire
        (B,) = sizes
        return int(B)

    @staticmethod
    def _pad_to(batch, B, target):
        """Pad every leading-dim leaf from B to target rows (repeating the
        last row) and inject the validity mask leaf."""
        pad = target - B

        def pad_leaf(x):
            x = np.asarray(x)
            if x.ndim == 0 or pad == 0:
                return x
            return np.concatenate([x, np.repeat(x[-1:], pad, axis=0)], axis=0)

        padded = jax.tree.map(pad_leaf, batch)
        mask = np.zeros((target,), np.float32)
        mask[:B] = 1.0
        padded[BATCH_MASK_KEY] = mask
        return padded

    def _pad_uneven_multihost(self, batch):
        """Multi-host uneven feeds: hosts may bring different local batch
        sizes (the reference's per-replica np.array_split allowed it); SPMD
        needs one per-device row count, so the hosts agree on it via a
        host-level allgather, each pads its slice to that multiple and
        injects its mask rows.  The engine's s_local*R/S weighting then
        reproduces the global weighted average across hosts.

        The skip decision is made AFTER the allgather from the gathered
        sizes (an ineligible batch reports -1), so no host can return early
        while the others block in the collective.
        """
        from jax.experimental import multihost_utils

        B = self._maskable_batch_size(batch)
        code = -1 if B is None else B
        all_b = np.asarray(multihost_utils.process_allgather(np.int32(code)))
        if (all_b < 0).any():
            # some host's batch is ineligible (mask already present / mixed
            # leading dims): every host skips so structures stay consistent
            return batch, 0
        spec = tuple(self._batch_spec)
        n0_local = self._spec_dim_size(spec[0]) // jax.process_count()
        # per-device rows must also divide into accum_steps microbatches
        A = self._t.accum_steps
        k = -(-int(all_b.max()) // max(1, n0_local))
        k = -(-k // A) * A
        target = k * n0_local
        if int(all_b.min()) == int(all_b.max()) and target == B:
            return batch, 0
        pad = target - B
        if pad < 0:
            raise ValueError(f"local batch {B} exceeds computed target {target}")
        if not self._warned_uneven:
            self._warned_uneven = True
            logging.warning(
                "Uneven multi-host feed (local %d, host sizes %s): padding "
                "to %d rows + '%s' mask per host.", B, all_b.tolist(),
                target, BATCH_MASK_KEY)
        return self._pad_to(batch, B, target), pad

    def _shard_batch(self, batch, _prepadded=False):
        with self._span("ad.shard_batch"):
            return self._shard_batch_impl(batch, _prepadded)

    def _shard_batch_impl(self, batch, _prepadded):
        spec = tuple(self._batch_spec)
        if self._batch_mask and not _prepadded:
            # (_prepadded: predict() already padded — skip, in particular
            # the multi-host path's cross-host allgather barrier)
            if self._multi_host:
                batch, _ = self._pad_uneven_multihost(batch)
            else:
                batch, _ = self._pad_uneven(batch)

        def put(x):
            x = np.asarray(x) if not isinstance(x, jax.Array) else x
            # leaves with fewer dims than the spec (e.g. (B,) labels under a
            # (replica, seq) spec) shard only their leading dims
            leaf_spec = P(*spec[:x.ndim])
            if self._multi_host:
                if isinstance(x, jax.Array) and not x.is_fully_addressable:
                    return x  # already a global array (e.g. prefetched)
                # host-local slices: divisibility/layout is validated by the
                # global-array conversion against per-host shard shapes
                from jax.experimental import multihost_utils

                return multihost_utils.host_local_array_to_global_array(
                    x, self._mesh, leaf_spec)
            entries = tuple(leaf_spec)
            if entries:
                n0 = self._spec_dim_size(entries[0])
                if x.ndim == 0 or x.shape[0] % n0 != 0:
                    raise ValueError(
                        f"Batch leading dimension must be divisible by the "
                        f"replica count ({n0}); got shape {x.shape}. For "
                        f"uneven dict batches pass distribute(..., "
                        f"batch_mask=True) with a loss that ignores "
                        f"'{BATCH_MASK_KEY}' rows (train_lib losses do).")
            for d, entry in enumerate(entries[1:], start=1):
                n = self._spec_dim_size(entry)
                if n > 1 and x.shape[d] % n != 0:
                    raise ValueError(
                        f"Batch dim {d} must be divisible by {n} (sharded "
                        f"over {entry}); got shape {x.shape}")
            return jax.device_put(x, NamedSharding(self._mesh, leaf_spec))

        return jax.tree.map(put, batch)

    # -- steady-state step (reference WrappedSession.run) ------------------

    def verify(self, batch, hbm_bytes_per_device=None, raise_on_error=True):
        """Statically verify the session's program against this batch's
        shapes (collective consistency, donation safety, HBM liveness —
        :mod:`autodist_tpu.analysis`).  Returns the Report; with
        ``raise_on_error`` ERROR findings raise StrategyVerificationError.
        """
        return self._verify_gbatch(self._shard_batch(batch),
                                   hbm_bytes_per_device=hbm_bytes_per_device,
                                   raise_on_error=raise_on_error)

    def _verify_gbatch(self, gbatch, hbm_bytes_per_device=None,
                       raise_on_error=True):
        from autodist_tpu.analysis import (DETERMINISM_PASSES,
                                           LOCKSTEP_PASSES, LOWERED_PASSES,
                                           STATIC_PASSES, TRACE_PASSES,
                                           verify_transformer)

        batch_shapes = jax.tree.map(
            lambda x: (tuple(x.shape), x.dtype), gbatch)
        # all five static tiers: the lowered audits (X-codes / F-codes)
        # surface realized reshards and compute waste, the lockstep tier
        # (L-codes) proves the schedule deadlock-free rank by rank, and
        # the determinism tier (N-codes) proves key independence + shard
        # disjointness, BEFORE the first step runs
        report = verify_transformer(
            self._t, batch_shapes, donate=self._donate,
            hbm_bytes_per_device=(hbm_bytes_per_device
                                  or self._verify_budget),
            passes=STATIC_PASSES + TRACE_PASSES + LOWERED_PASSES
            + LOCKSTEP_PASSES + DETERMINISM_PASSES)
        if report.findings:
            logging.info("Strategy verification:\n%s", report)
        if raise_on_error:
            report.raise_for_errors()
        return report

    def _pre_step(self, gbatch):
        """First-step hooks shared by both run paths: opt-in verification
        + the 4-stage program-evolution dump (no-op unless
        AUTODIST_DUMP_HLO) — the analog of the reference's per-pass
        TensorBoard graph logging."""
        if self._verify and not self._verified:
            # abstractly re-trace and verify against this batch's shapes
            # before anything executes
            self._verified = True
            self._verify_gbatch(gbatch)
        if not self._dumped_artifacts:
            self._dumped_artifacts = True
            from autodist_tpu.utils.visualization_util import (
                dump_step_artifacts)

            dump_step_artifacts(self._t, self._step, self.state, gbatch)

    def _trace_step_dir(self, trace_dir, step):
        """Per-step profile dir: repeated traced runs must not overwrite
        each other's capture (``<trace_dir>/step_<n>/``)."""
        path = os.path.join(trace_dir, f"step_{step}")
        os.makedirs(path, exist_ok=True)
        return path

    def run(self, batch, trace_dir=None):
        """One training step on a global batch; returns the metrics dict.

        With ``trace_dir`` the step runs under ``jax.profiler.trace`` in
        ``<trace_dir>/step_<n>/`` (namespaced so repeated traced runs
        keep every capture) and the metrics carry the capture path under
        ``"trace_dir"``.

        Without telemetry this is the hot path: one async dispatch, no
        host sync (unless tracing), no file I/O.  With it (``tel``), the
        step's wall time is closed at a real sync point and the watchdog
        may arm a capture.  Both carry the same ``ad.*`` spans, inert
        while no profile is taken.
        """
        tel = self._telemetry
        with jax.profiler.StepTraceAnnotation(
                "ad.run", step_num=self._dispatches) as run_span:
            gbatch = self._shard_batch(batch)
            with self._span("ad.pre_step"):
                self._pre_step(gbatch)
            path = capture_dir = None
            if trace_dir:
                path = self._trace_step_dir(trace_dir, self.step)
            elif tel is not None:
                path = capture_dir = tel.arm_capture_dir()
                if capture_dir:
                    os.makedirs(capture_dir, exist_ok=True)
            if tel is not None:
                tel.step_started()
            if path:
                with jax.profiler.trace(path):
                    metrics = self._dispatch(gbatch)
                    jax.block_until_ready(metrics)
            else:
                metrics = self._dispatch(gbatch)
            variants = self._step._cache_size()
            run_span.set_metadata(variants=variants,
                                  **self._finished_aux(metrics))
            if variants > self._variants:
                self._note_compiled(variants)
            self._dispatches += 1
            if tel is not None:
                tel.step_finished(metrics, gbatch, trace_dir=path,
                                  watchdog_capture=capture_dir is not None)
        if path:
            metrics = dict(metrics)
            metrics["trace_dir"] = path
        return metrics

    def _finished_aux(self, metrics):
        """Arguments for ``ad.run`` from a loss's auxiliary outputs (scalars
        the model counts, e.g. ``moe_rows_here``): those of the newest
        earlier dispatch whose step has finished, with ``aux_step`` saying
        which.  A step is still running when its ``run`` returns, so its own
        are not there yet; nothing here waits for the device, and a loss
        without auxiliary outputs costs one comparison."""
        aux = {k: v for k, v in metrics.items()
               if k not in ("loss", "step", "grad_norm")
               and getattr(v, "ndim", None) == 0} \
            if isinstance(metrics, dict) else {}
        if not aux and not self._aux_pending:
            return {}
        done = None
        while self._aux_pending and all(
                v.is_ready() for v in self._aux_pending[0][1].values()):
            done = self._aux_pending.popleft()
        if aux:
            self._aux_pending.append((self._dispatches, aux))
        if done is None:
            return {}
        return {"aux_step": done[0],
                **{k: float(v) for k, v in done[1].items()}}

    def _dispatch(self, gbatch):
        with self._span("ad.dispatch"):
            self.state, metrics = self._step(self.state, gbatch)
        return metrics

    def _note_compiled(self, variants):
        """The step's compiled variants grew with this dispatch: the
        program's own compile count.  The first is the expected compile;
        any later one means a batch of another shape, dtype or sharding,
        and gets a warning that names the dispatch."""
        if self._variants:
            logging.warning(
                "the training step compiled again at dispatch %d (%d "
                "variants now): its batch differs in shape, dtype or "
                "sharding from the ones before", self._dispatches, variants)
        self._variants = variants

    @staticmethod
    def _metrics_log_str(metrics):
        """Loggable rendering of a step's metrics: the loss when present,
        otherwise every scalar entry — a model without a ``"loss"`` key
        must not crash the training loop's progress log."""
        if isinstance(metrics, dict) and "loss" in metrics:
            return f"loss={float(metrics['loss'])}"
        scalars = []
        if isinstance(metrics, dict):
            for k, v in metrics.items():
                try:
                    if np.ndim(v) == 0:
                        scalars.append(f"{k}={float(v)}")
                except (TypeError, ValueError):
                    continue
        return " ".join(scalars) if scalars else f"metrics={metrics!r}"

    def finalize_telemetry(self):
        """Flush the telemetry summary / manifest for this session (no-op
        when telemetry is off).  ``run_steps`` and ``fit`` call it on
        exit; call it yourself after a hand-rolled ``run()`` loop."""
        if self._telemetry is not None:
            return self._telemetry.finalize()
        return None

    def _preempt_path(self, preempt_checkpoint_dir):
        return os.path.join(preempt_checkpoint_dir, "preempt_ckpt")

    def _preempt_save(self, preempt_checkpoint_dir):
        """Drain + write the preemption checkpoint (manifest, update-space
        layout: no gather on save — the preemption window is short)."""
        from autodist_tpu.checkpoint.saver import Saver

        jax.block_until_ready(self.state)
        path = Saver(self).save_sharded(
            self._preempt_path(preempt_checkpoint_dir))
        logging.warning(
            "Preemption checkpoint written to %s (step %d); exiting the "
            "training loop cleanly", path, self.step)
        self.preempted = True
        return path

    def _preempt_resume(self, preempt_checkpoint_dir):
        """Resume from a preemption checkpoint when one exists AND is
        ahead of the session's current step (a periodic checkpoint_path
        restore may already be newer)."""
        from autodist_tpu.checkpoint.manifest import load_manifest
        from autodist_tpu.checkpoint.saver import Saver

        path = self._preempt_path(preempt_checkpoint_dir)
        if not Saver.exists(path):
            return
        m = load_manifest(path)
        if m is not None and int(m["step"]) <= self.step:
            return
        Saver(self).restore(path)
        logging.info("Resumed from preemption checkpoint %s at step %d",
                     path, self.step)

    def run_steps(self, batches, log_every=0, preempt_checkpoint_dir=None):
        """Run a sequence of steps.  With ``preempt_checkpoint_dir`` a
        SIGTERM/SIGINT drains the in-flight step, writes a manifest
        checkpoint there and returns cleanly (see :meth:`fit`)."""
        metrics = None
        with PreemptionGuard() if preempt_checkpoint_dir else \
                contextlib.nullcontext() as guard:
            for i, b in enumerate(batches):
                metrics = self.run(b)
                if log_every and (i + 1) % log_every == 0:
                    logging.info("step %d: %s", i + 1,
                                 self._metrics_log_str(metrics))
                if guard is not None and guard.requested:
                    self._preempt_save(preempt_checkpoint_dir)
                    break
        self.finalize_telemetry()
        return metrics

    def fit(self, batch_fn, steps, *, checkpoint_path=None, save_every=0,
            log_every=0, resume=True, preempt_checkpoint_dir=None):
        """Managed training loop: periodic checkpoints + crash resume.

        ``batch_fn(step) -> batch`` supplies the step's global batch (a
        callable rather than an iterator so a resumed run can re-enter the
        stream at the restored step).  With ``checkpoint_path``, the loop
        restores the latest checkpoint on entry (``resume=True``), saves
        every ``save_every`` steps and at the end — so a preempted or
        crashed job re-run with the same arguments continues where it left
        off (the reference's fail-fast coordinator offers no recovery; this
        is the TPU-pod-preemption story on top of the Saver contract).

        ``preempt_checkpoint_dir`` opts into the SIGTERM/SIGINT preemption
        hook (:class:`PreemptionGuard`): on a signal the in-flight step
        drains, a manifest (update-space, no-gather) checkpoint lands in
        ``<dir>/preempt_ckpt``, and ``fit`` returns cleanly with
        ``self.preempted`` set — re-running with the same arguments
        resumes from it (topology changes go through
        :class:`autodist_tpu.elastic.ElasticTrainer`, which reshards).
        """
        saver = None
        self.preempted = False
        if checkpoint_path:
            from autodist_tpu.checkpoint.saver import Saver

            saver = Saver(self)
            if resume:
                # "start fresh" is decided by an existence PROBE, not by
                # the restore's exception type: remote stores raise
                # backend-specific errors (not FileNotFoundError) for an
                # absent path, and a genuine store error during restore
                # must fail loudly, not silently restart at step 0
                if Saver.exists(checkpoint_path):
                    saver.restore(checkpoint_path)
                    logging.info("fit: resumed from %s at step %d",
                                 checkpoint_path, self.step)
                else:
                    logging.info("fit: no checkpoint at %s; starting fresh",
                                 checkpoint_path)
        if preempt_checkpoint_dir and resume:
            self._preempt_resume(preempt_checkpoint_dir)
        metrics = None
        last_saved = -1
        with PreemptionGuard() if preempt_checkpoint_dir else \
                contextlib.nullcontext() as guard:
            while self.step < steps:
                step = self.step
                metrics = self.run(batch_fn(step))
                done = self.step
                if log_every and done % log_every == 0:
                    logging.info("step %d: %s", done,
                                 self._metrics_log_str(metrics))
                if guard is not None and guard.requested:
                    self._preempt_save(preempt_checkpoint_dir)
                    break
                if saver and save_every and done % save_every == 0:
                    saver.save(checkpoint_path)
                    last_saved = done
        if (saver and self.step != last_saved and metrics is not None
                and not self.preempted):
            saver.save(checkpoint_path)
        self.finalize_telemetry()
        return metrics

    def memory_stats(self):
        """Per-device live/peak memory (bytes) when the backend reports it
        (TPU does; CPU returns None entries)."""
        return {str(d): d.memory_stats() if hasattr(d, "memory_stats") else None
                for d in self._mesh.devices.flat}

    # -- fetches (reference remapper._remap_fetch analog) ------------------

    def params(self):
        """Full, unpadded parameter pytree (replicated layout), as the
        original single-device program would see it."""
        return jax.device_get(self._t.canonicalize_params(self.state["params"]))

    def predict(self, batch, apply_fn=None):
        """Forward-only evaluation on a global batch (reference remapper
        fetch contraction: per-replica outputs concatenate back into the
        global-batch order).

        ``apply_fn(params, batch) -> outputs`` — or, when the session was
        built with ``mutable_state``, ``apply_fn(params, state, batch)``.
        Defaults to the ModelItem's ``eval_fn``.  Pass a *stable* function
        reference (not a fresh lambda per call): each distinct function
        compiles its own jitted program (cache capped at 8).
        """
        apply_fn = apply_fn or self._t.model_item.eval_fn
        if apply_fn is None:
            raise ValueError("No eval_fn: pass apply_fn or distribute(eval_fn=...)")
        # the cache holds a strong reference to apply_fn so its id cannot be
        # recycled by GC and collide with a dead function's entry
        key = id(apply_fn)
        has_mutable = self.state["mutable"] is not None
        if key not in self._eval_cache:
            if len(self._eval_cache) >= 8:
                self._eval_cache.pop(next(iter(self._eval_cache)))  # FIFO
            t = self._t

            def eval_step(storage, mutable, b):
                params = t.canonicalize_params(storage)
                if has_mutable:
                    return apply_fn(params, mutable, b)
                return apply_fn(params, b)

            self._eval_cache[key] = (apply_fn, jax.jit(eval_step))
        # padding gates on the same opt-in as training: a batch-reduced
        # apply_fn (e.g. a mean metric) would silently include pad rows.
        # Pad BEFORE _shard_batch on both paths so the local pad count is
        # known and per-example outputs can be trimmed symmetrically
        # (multi-host trims its host-local slice after fetch contraction).
        pad = 0
        if self._batch_mask:
            if self._multi_host:
                batch, pad = self._pad_uneven_multihost(batch)
            else:
                batch, pad = self._pad_uneven(batch)
        out = self._eval_cache[key][1](
            self.state["params"], self.state["mutable"],
            self._shard_batch(batch, _prepadded=self._batch_mask))
        if self._multi_host:
            from jax.experimental import multihost_utils

            spec = tuple(self._batch_spec)
            out_specs = jax.tree.map(lambda x: P(*spec[:x.ndim]), out)
            out = multihost_utils.global_array_to_host_local_array(
                out, self._mesh, out_specs)
        else:
            out = jax.device_get(out)
        if pad:
            padded_b = np.shape(batch[BATCH_MASK_KEY])[0]
            out = jax.tree.map(
                lambda x: x[:padded_b - pad]
                if np.ndim(x) >= 1 and np.shape(x)[0] == padded_b else x, out)
        return out

    def check_replication(self, atol=0.0):
        """Debug guard: verify all REPLICATED storage really is identical
        across devices.  Catches silent divergence (e.g. a variable with an
        unsynchronized device-local gradient contribution).  Returns the
        list of offending variable names (empty = healthy)."""
        t = self._t
        bad = []
        leaves = t.treedef.flatten_up_to(self.state["params"])
        for name, leaf in zip(t.names, leaves):
            if t.plans[name].placement is not Placement.REPLICATED:
                continue
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            for s in shards[1:]:
                if not np.allclose(shards[0], s, atol=atol, rtol=0):
                    bad.append(name)
                    break
        return bad

    def mutable_state(self):
        """Current non-trainable state (e.g. batch stats), host-fetched."""
        return jax.device_get(self.state["mutable"])

    @property
    def step(self):
        return int(self.state["step"])
