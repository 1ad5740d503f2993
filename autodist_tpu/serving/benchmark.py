"""Serving decode benchmark: continuous batching vs the static rollout.

Measures the SAME request set twice on the current backend — once
through :class:`~autodist_tpu.serving.engine.ServingEngine` (one jitted
vmapped decode step over the slot axis, requests admitted between
steps) and once through the static per-request
:func:`~autodist_tpu.models.decoding.generate` rollouts — and reports
the machine-normalized wall ratio ``serving_decode_overhead``
(engine wall / static wall; < 1 means continuous batching wins).  The
ratio cancels host speed, so the committed
``records/cpu_mesh/gpt_tiny_serve_decode.json`` record diffs cleanly
against its blessed baseline across hosts (``make perf-gate``), keeping
the serving tier's tokens/sec overhead trajectory observable between
chip windows — the same role ``cpu_mesh_engine_overhead`` plays for
training.  Entry points: ``examples/benchmark.py --serve`` (writes the
record), ``tools/perf_gate.py`` (re-measures and gates).
"""
import time
from autodist_tpu.utils.rng import host_key

SERVE_PROXY_METRIC = "serving_decode_overhead"
SERVE_RECORD_NAME = "gpt_tiny_serve_decode"

# (prompt, max_new_tokens) per request: varied prompt lengths so the
# measurement exercises the shared-executable path, sized to finish in a
# few dozen CPU decode steps
REQUESTS = (((5, 7, 9), 8), ((11, 3, 2, 8, 1), 7), ((42,), 10),
            ((9, 9, 9, 9), 6))
MAX_TOTAL = 24
NUM_SLOTS = 4


def measure_serve_decode(num_slots=NUM_SLOTS, max_total=MAX_TOTAL,
                         requests=REQUESTS, repeats=2):
    """Return the serving-overhead record dict (see module docstring)."""
    import numpy as np

    import jax

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.models.decoding import generate
    from autodist_tpu.models.gpt import GPT, GPT_TINY
    from autodist_tpu.resource_spec import ResourceSpec

    cfg = GPT_TINY
    model = GPT(cfg, decode=True)
    params = model.init(host_key(0),
                        np.zeros((1, 1), np.int32))["params"]
    n = jax.device_count()
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(n))
    eng = ad.serve(model, params, max_total=max_total, num_slots=num_slots,
                   telemetry=False)

    prompts = [np.asarray([p], np.int32) for p, _ in requests]

    def run_static():
        for (p, k), arr in zip(requests, prompts):
            np.asarray(generate(model, cfg.max_position, params, arr, k))

    def run_engine():
        for p, k in requests:
            eng.submit(p, k)
        eng.run()

    run_static()   # warmup: compile every (prompt_len, total) rollout
    run_engine()   # warmup: compile the batch step + admit executables

    t_static = t_engine = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        run_static()
        t_static += time.perf_counter() - t0
        t0 = time.perf_counter()
        run_engine()
        t_engine += time.perf_counter() - t0

    new_tokens = repeats * sum(k for _, k in requests)
    return {
        "schema": 1,
        "name": SERVE_RECORD_NAME,
        "metric": SERVE_PROXY_METRIC,
        "backend": jax.default_backend(),
        "num_devices": n,
        "slots": num_slots,
        "requests": len(requests),
        "new_tokens": new_tokens,
        # machine-normalized: engine continuous-batching wall over the
        # static per-request rollout wall for the same request set
        "serving_decode_overhead": round(t_engine / max(t_static, 1e-9), 3),
        "engine_tokens_per_s": round(new_tokens / max(t_engine, 1e-9), 1),
        "generate_tokens_per_s": round(new_tokens / max(t_static, 1e-9), 1),
        # machine absolutes: reported, never gated
        "info": {"engine_wall_ms": round(t_engine * 1e3, 2),
                 "generate_wall_ms": round(t_static * 1e3, 2)},
        "note": ("CPU-mesh pipeline proxy — serving-engine overhead vs "
                 "the static rollout, never a hardware throughput claim"),
    }
