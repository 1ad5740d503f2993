"""AutoDist: the user entry point.

Reference ``autodist/autodist.py:60-322``: one instance per process wraps a
resource spec + strategy builder; ``scope()`` captures the model;
``create_distributed_session()`` builds-or-loads the strategy (chief builds
and serializes, workers deserialize by ``AUTODIST_STRATEGY_ID``), compiles
it, transforms the graph and returns a wrapped session.

TPU-native UX (no graph capture needed — models are functions)::

    ad = AutoDist("resource_spec.yml", AllReduce())
    sess = ad.distribute(loss_fn, params, optax.adam(1e-3))
    for batch in data:
        metrics = sess.run(batch)

``loss_fn(params, batch[, rng]) -> loss`` is single-device code; the
framework distributes it according to the strategy.
"""
import contextlib
import functools
from typing import Any, Callable, Optional, Sequence

from autodist_tpu import const
from autodist_tpu.const import ENV
from autodist_tpu.model_item import ModelItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy.base import Strategy, StrategyCompiler
from autodist_tpu.telemetry import setup
from autodist_tpu.utils import logging
from autodist_tpu.utils.compile_cache import ensure_compile_cache

_DEFAULT_AUTODIST = {}


def _set_up(entry):
    """An entry point that builds a session (or compiles its step) runs
    inside the set-up span ``ad.distribute`` (telemetry/setup.py)."""
    @functools.wraps(entry)
    def spanned(*args, **kwargs):
        with setup.span("ad.distribute"):
            return entry(*args, **kwargs)
    return spanned


def _strategy_requests_async(proto):
    """True when any node (or partition shard) carries an async
    PSSynchronizer (sync=False) — the strategy-level switch into the
    host-PS async runtime."""
    for n in proto.node_config:
        for src in (n, *n.part_config):
            if (src.WhichOneof("synchronizer") == "PSSynchronizer"
                    and not src.PSSynchronizer.sync):
                return True
    return False


def set_default_autodist(o):
    """One AutoDist per process (reference autodist.py:43-57)."""
    if _DEFAULT_AUTODIST and ENV.AUTODIST_IS_TESTING.val is False:
        raise NotImplementedError("Only one AutoDist instance is supported per process")
    _DEFAULT_AUTODIST["instance"] = o


def get_default_autodist():
    return _DEFAULT_AUTODIST.get("instance")


class AutoDist:
    def __init__(self, resource_spec_file=None, strategy_builder=None, *,
                 resource_spec: Optional[ResourceSpec] = None):
        set_default_autodist(self)
        ensure_compile_cache()
        self._resource_spec = resource_spec or ResourceSpec(resource_spec_file)
        if strategy_builder is None:
            from autodist_tpu.strategy import PSLoadBalancing

            strategy_builder = PSLoadBalancing()  # reference default, autodist.py:70
        self._strategy_builder = strategy_builder
        self._mesh = None

    @property
    def resource_spec(self):
        return self._resource_spec

    @property
    def is_chief(self):
        return const.IS_AUTODIST_CHIEF

    @property
    def mesh(self):
        if self._mesh is None:
            from autodist_tpu.parallel.mesh import build_mesh

            self._mesh = build_mesh(self._resource_spec)
        return self._mesh

    def rebind(self, resource_spec):
        """Elastic re-plan entry (docs/elasticity.md): swap in the
        SURVIVING topology's spec (usually ``old_spec.shrink(...)``) and
        drop the cached mesh, so the next :meth:`distribute` plans —
        AutoStrategy re-enumerates, builders re-factor the mesh — against
        what is actually alive.  Sessions built before the rebind keep
        their old mesh; the elastic driver rebuilds the session and
        reshards the checkpoint onto it
        (:func:`autodist_tpu.checkpoint.reshard.reshard_restore`)."""
        self._resource_spec = resource_spec
        self._mesh = None
        return self

    def _mesh_for(self, strategy):
        """The session mesh for a compiled strategy.  Normally the spec's
        mesh (``build_mesh``); when the strategy's ``graph_config.mesh``
        declares the ``replica_dcn x replica_ici`` factorization (a
        two-level builder wrote its host-boundary split there) and the
        YAML carries no explicit ``mesh:`` request, the factored mesh is
        built so the TWO_LEVEL schedule can realize."""
        from autodist_tpu.const import AXIS_REPLICA_DCN, AXIS_REPLICA_ICI
        from autodist_tpu.parallel.mesh import build_mesh

        gm = strategy.proto.graph_config.mesh
        names = tuple(gm.axis_names)
        if (self._resource_spec.mesh_request is None
                and AXIS_REPLICA_DCN in names and AXIS_REPLICA_ICI in names):
            axes = dict(zip(names, (int(s) for s in gm.axis_sizes)))
            return build_mesh(self._resource_spec, axes=axes)
        return self.mesh

    # -- strategy lifecycle (reference autodist.py:100-118) ----------------

    def _build_or_load_strategy(self, model_item) -> Strategy:
        if self.is_chief:
            strategy = self._strategy_builder.build(model_item, self._resource_spec)
            strategy.serialize()
            logging.info("Chief built strategy %s", strategy.id)
        else:
            sid = ENV.AUTODIST_STRATEGY_ID.val
            if not sid:
                raise RuntimeError("Worker process missing AUTODIST_STRATEGY_ID")
            strategy = Strategy.deserialize(sid)
            logging.info("Worker loaded strategy %s", strategy.id)
        return strategy

    def build_strategy(self, model_item) -> Strategy:
        """Build (or load) + compile the strategy for a captured model."""
        raw = self._build_or_load_strategy(model_item)
        return self._compile_strategy(model_item, raw)

    def _compile_strategy(self, model_item, raw) -> Strategy:
        # all hosts must realize the identical program; check BEFORE compiling
        # so a mismatch fails with a clear message (utils/consistency)
        from autodist_tpu.utils.consistency import verify_agreement

        verify_agreement(raw.proto.SerializeToString(), "strategy")
        return StrategyCompiler(model_item, self._resource_spec).compile(raw)

    def _capture(self, loss_fn, params, optimizer, remat=False, **capture):
        """The captured model and its strategy as built or loaded."""
        with setup.span("ad.capture"):
            if remat:
                import jax

                loss_fn = jax.checkpoint(loss_fn)
            item = ModelItem(loss_fn, params, optimizer, **capture)
        with setup.span("ad.strategy"):
            return item, self._build_or_load_strategy(item)

    # -- main entry --------------------------------------------------------

    @_set_up
    def distribute(
        self,
        loss_fn: Callable,
        params: Any,
        optimizer: Any,
        *,
        sparse_vars: Optional[Sequence[str]] = None,
        has_aux: bool = False,
        has_rng: bool = False,
        mutable_state: Any = None,
        eval_fn: Callable = None,
        rng=None,
        name: str = "",
        donate: bool = True,
        remat: bool = False,
        data_axes=None,
        batch_spec=None,
        accum_steps: int = 1,
        clip_global_norm=None,
        param_specs=None,
        batch_mask: bool = False,
        sync_schedule: Optional[str] = None,
        verify: bool = False,
    ):
        """Capture single-device code and return a distributed session.

        ``verify=True`` runs the static strategy verifier
        (:mod:`autodist_tpu.analysis`, docs/analysis.md): the strategy and
        sharding lint runs immediately (build time), and the first
        ``run()`` abstractly re-traces the step against the real batch
        shapes to check collective consistency, donation safety and the
        HBM liveness peak — raising
        :class:`~autodist_tpu.analysis.StrategyVerificationError` on
        ERROR-level findings instead of hanging a pod.

        ``remat=True`` wraps the loss in ``jax.checkpoint`` — trade FLOPs
        for HBM by rematerializing activations in the backward pass.

        ``sync_schedule`` overrides the strategy's gradient-sync issue
        schedule: ``"overlap"`` pipelines per-bucket collectives behind
        backward compute (XLA latency-hiding scheduler), ``"barrier"``
        syncs once after the full backward; ``None`` follows the
        strategy's ``AllReduceSynchronizer.schedule``.

        ``batch_mask=True`` enables uneven global batches: non-divisible
        dict batches are padded and given a ``const.BATCH_MASK_KEY`` leaf,
        and the engine weights each device's loss so the update equals the
        reference's weighted average (``remapper.py:109-118``).  The loss
        MUST exclude masked rows from its local mean (all
        ``models.train_lib`` losses do when the mask is present).
        """
        item, raw = self._capture(
            loss_fn, params, optimizer, remat=remat, sparse_vars=sparse_vars,
            has_aux=has_aux, has_rng=has_rng, mutable_state=mutable_state,
            eval_fn=eval_fn, name=name)
        return self._assemble_session(
            item, raw, rng=rng, donate=donate, batch_mask=batch_mask,
            verify=verify, data_axes=data_axes, batch_spec=batch_spec,
            accum_steps=accum_steps, clip_global_norm=clip_global_norm,
            param_specs=param_specs, sync_schedule=sync_schedule)

    def _assemble_session(self, item, raw, *, rng, donate, batch_mask,
                          async_authkey=None, verify=False,
                          **transformer_kwargs):
        """Shared tail of :meth:`distribute` and :meth:`launch`: verify
        cross-host agreement, compile, transform, wrap in a session."""
        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.runner import DistributedSession

        with setup.span("ad.strategy_compile"):
            strategy = self._compile_strategy(item, raw)
        if _strategy_requests_async(strategy.proto):
            # PS(sync=False, ...) selects TRUE asynchrony through the user
            # API (reference: staleness/async is a strategy field,
            # ``proto/synchronizers.proto:25-35``) — an SPMD program is
            # bulk-synchronous, so this runs the host-PS async runtime
            # instead of the shard_map engine.  Options only the SPMD
            # engine implements are REJECTED loudly, never dropped.
            unsupported = {
                k: v for k, v in dict(
                    batch_mask=batch_mask or None, rng=rng,
                    verify=verify or None,
                    **{kk: vv for kk, vv in transformer_kwargs.items()
                       if vv is not None
                       and not (kk == "accum_steps" and vv == 1)},
                ).items() if v is not None}
            if unsupported:
                raise NotImplementedError(
                    f"async PS runtime (sync=False) does not support "
                    f"{sorted(unsupported)}; use the synchronous engine "
                    f"or drop these options")
            n_nodes = len(self._resource_spec.node_addresses)
            if n_nodes > 1 or ENV.AUTODIST_NUM_PROCESSES.val > 1:
                # multi-process deployment: the chief serves the TCP PS,
                # every rank (chief included) drives one worker — the
                # reference's PS-reachable-from-AutoDist() shape
                # (server_starter.py:50-76) through the front door.  The
                # barrier size comes from the SPEC when it is multi-node
                # (the chief's own env never carries
                # AUTODIST_NUM_PROCESSES — worker_env only hands it to
                # workers), falling back to the env contract for
                # spec-less worker processes.
                from autodist_tpu.kernel.synchronization.async_service import (
                    AsyncPSClusterSession)

                return AsyncPSClusterSession(
                    strategy, item, run_id=raw.id,
                    num_workers=(n_nodes if n_nodes > 1
                                 else ENV.AUTODIST_NUM_PROCESSES.val),
                    chief_host=self._resource_spec.chief,
                    authkey=async_authkey)
            from autodist_tpu.kernel.synchronization.async_ps import (
                AsyncPSEngineSession)

            return AsyncPSEngineSession(strategy, item)
        if verify:
            # build-time half of the verifier: strategy/sharding lint +
            # static HBM terms fail FAST (the traced passes run on the
            # session's first step, when batch shapes are known)
            from autodist_tpu.analysis import STATIC_PASSES, verify_strategy

            report = verify_strategy(
                strategy, item, self._resource_spec,
                param_specs=transformer_kwargs.get("param_specs"),
                passes=STATIC_PASSES)
            report.raise_for_errors()
        with setup.span("ad.transform"):
            transformer = GraphTransformer(
                strategy, item, self._mesh_for(strategy), **transformer_kwargs)
        return DistributedSession(transformer, rng=rng, donate=donate,
                                  batch_mask=batch_mask, verify=verify)

    # parity alias with the reference's create_distributed_session
    create_distributed_session = distribute

    @_set_up
    def launch(self, loss_fn, params, optimizer, *, coordinator_port=None,
               **kwargs):
        """Full multi-host entry (reference ``create_distributed_session``
        + ``Coordinator.launch_clients``, ``coordinator.py:46-90``): on the
        chief, build + serialize the strategy, SSH-launch every worker
        (re-executing this script with the ``AUTODIST_*`` env contract),
        and join the ``jax.distributed`` group; on workers (re-executed by
        the chief), join the group and load the strategy by id.  All hosts
        then verify byte-identical strategies and build the same SPMD
        session.

        The strategy serialization dir (``const.DEFAULT_SERIALIZATION_DIR``)
        must be visible to the workers (shared filesystem), matching the
        reference's NFS assumption for its strategy handoff.

        Single-node specs degrade to plain :meth:`distribute`.
        """
        from autodist_tpu.cluster import Coordinator

        capture_keys = ("remat", "sparse_vars", "has_aux", "has_rng",
                        "mutable_state", "eval_fn", "name")
        item, raw = self._capture(
            loss_fn, params, optimizer,
            **{k: kwargs.pop(k) for k in capture_keys if k in kwargs})

        kw = {} if coordinator_port is None else {
            "coordinator_port": coordinator_port}
        coordinator = Coordinator(self._resource_spec, **kw)
        self._coordinator = coordinator  # keep monitors/terminate reachable
        session_kwargs = dict(
            rng=kwargs.pop("rng", None),
            donate=kwargs.pop("donate", True),
            batch_mask=kwargs.pop("batch_mask", False),
            **kwargs)
        if _strategy_requests_async(raw.proto):
            # async runtime: each process drives only its LOCAL devices
            # through the host PS, so there is no SPMD group to join —
            # skip jax.distributed.  The chief BINDS the service first
            # (assemble), then publishes the BOUND address into the env
            # the workers are LAUNCHED with (launch-scoped extra_env —
            # never the chief's own os.environ, which a second launch()
            # in this process would read back as a stale address), so an
            # ephemeral-port (":0") request reaches them resolved.  The
            # chief also mints a random 256-bit session token here — it
            # launches every worker, so the token rides the same env
            # contract; only externally-scheduled deployments fall back
            # to the derived authkey (async_service.resolve_authkey).
            cl = coordinator.cluster
            chief_launches = cl.num_processes > 1 and cl.is_chief
            authkey = None
            if chief_launches:
                import secrets

                authkey = secrets.token_bytes(32)
            sess = self._assemble_session(item, raw, async_authkey=authkey,
                                          **session_kwargs)
            if chief_launches:
                extra = {"AUTODIST_ASYNC_PS_AUTHKEY": authkey.hex()}
                if getattr(sess, "address", None):
                    extra["AUTODIST_ASYNC_PS_ADDR"] = sess.address
                cl.launch_workers(raw.id, extra_env=extra)
            return sess
        coordinator.setup(raw)  # chief launches workers; everyone joins
        return self._assemble_session(item, raw, **session_kwargs)

    @_set_up
    def aot_compile(self, loss_fn, params, optimizer, *, batch_shapes,
                    topology="v5e:2x2", **kwargs):
        """Compile the distributed training step for a DEVICELESS TPU
        topology — compile errors, HBM demand, and cost analysis for the
        target generation before a single chip is attached (the
        deploy-before-the-pod-is-up workflow; see
        :mod:`autodist_tpu.aot`)."""
        from autodist_tpu.aot import aot_compile_step

        return aot_compile_step(self, loss_fn, params, optimizer,
                                batch_shapes=batch_shapes,
                                topology=topology, **kwargs)

    def serve(self, model, params, *, max_total, num_slots=4,
              temperature=0.0, policy=None, telemetry=True,
              prefill_fraction=0.0, event_log=None, run_dir=None,
              **kwargs):
        """Serving entrypoint (``docs/serving.md``): a continuous-
        batching decode :class:`~autodist_tpu.serving.engine.
        ServingEngine` over this AutoDist's devices.

        ``model`` is the ``decode=True`` flax module, ``params`` its
        trained parameters (e.g. from a finished :meth:`distribute`
        session); ``max_total`` bounds prompt + new tokens per slot.
        ``prefill_fraction > 0`` carves that share of the devices off as
        a disaggregated prefill subset; the rest shard the slot axis
        (when ``num_slots`` divides them evenly).  ``telemetry=True``
        attaches a schema-v5 :class:`~autodist_tpu.serving.telemetry.
        ServingTelemetry`; submit with ``engine.submit(prompt, n)``,
        drive with ``engine.run()``, close with ``engine.finalize()``.
        """
        import numpy as np
        from jax.sharding import Mesh

        from autodist_tpu.serving import ServingEngine, ServingTelemetry
        from autodist_tpu.serving.slots import SLOT_AXIS

        devs = list(self.mesh.devices.flat)
        prefill = []
        if prefill_fraction > 0 and len(devs) > 1:
            k = min(max(1, int(len(devs) * prefill_fraction)),
                    len(devs) - 1)
            prefill, devs = devs[-k:], devs[:-k]
        mesh = None
        if len(devs) > 1 and num_slots % len(devs) == 0:
            mesh = Mesh(np.asarray(devs), (SLOT_AXIS,))
        tel = ServingTelemetry(run_dir=run_dir, num_devices=len(devs)) \
            if telemetry else None
        return ServingEngine(
            model, params, max_total=max_total, num_slots=num_slots,
            temperature=temperature, policy=policy, telemetry=tel,
            mesh=mesh, prefill_devices=prefill, event_log=event_log,
            **kwargs)

    @contextlib.contextmanager
    def scope(self):
        """Parity with the reference's ``ad.scope()`` (autodist.py:309-322).

        In the reference this captures the TF default graph; in the
        functional world there is no implicit graph, so the scope simply
        marks this AutoDist as the process default for the block — model
        code built inside may consult :func:`get_default_autodist`.
        """
        prev = _DEFAULT_AUTODIST.pop("instance", None)
        _DEFAULT_AUTODIST["instance"] = self
        try:
            yield self
        finally:
            if prev is None:
                _DEFAULT_AUTODIST.pop("instance", None)
            else:
                _DEFAULT_AUTODIST["instance"] = prev

    def function(self, loss_fn, params, optimizer, **kwargs):
        """Reference ``autodist.function`` UX (``autodist.py:201-289``):
        returns a plain callable ``step(batch) -> metrics`` that builds the
        distributed session lazily on first call and reuses it after."""
        box = {}

        def step(batch):
            if "sess" not in box:
                box["sess"] = self.distribute(loss_fn, params, optimizer, **kwargs)
            return box["sess"].run(batch)

        step.session = lambda: box.get("sess")
        return step
