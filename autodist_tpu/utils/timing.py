"""Step timing under asynchronous dispatch.

JAX dispatch is async: a timed window has to end at a point where the
device has really finished.  ``measure_per_step`` closes its windows with
a host fetch of one device scalar — the bytes cannot arrive before the
program producing them finishes — and differences two windows so that
whatever a window costs once (the fetch, the dispatch ramp) cancels:

  1. run K *dependent* steps (each consuming the previous state, so the
     device cannot reorder or elide them), fetch ONE scalar -> T(K);
  2. run 2K steps the same way -> T(2K);
  3. per-step = (T(2K) - T(K)) / K.

``chip_smoke.py`` prints this next to the plain K-steps-then-
``block_until_ready`` reading; ROADMAP S1 keeps one of the two.  The
reference's benchmark harness could time with wall clock because TF
session.run is synchronous (``examples/benchmark/utils/...``); this module
is the async-dispatch analog of that timing discipline.
"""
import time

import jax
import numpy as np

# bf16 peak FLOPs/s per chip, by jax device_kind (public spec numbers).
# Prefix-matched longest-first so "TPU v5 lite" does not hit "TPU v5".
PEAK_BF16_FLOPS = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
}


def peak_flops(device=None):
    """Peak bf16 FLOP/s of a device (default: device 0).  A ``device_kind``
    with no entry in ``PEAK_BF16_FLOPS`` raises ``KeyError``: a utilization
    against another device's peak is not a number."""
    device = device or jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    for key in sorted(PEAK_BF16_FLOPS, key=len, reverse=True):
        if kind.startswith(key):
            return PEAK_BF16_FLOPS[key]
    raise KeyError(f"no bf16 peak known for device_kind {kind!r}; add it to "
                   "autodist_tpu.utils.timing.PEAK_BF16_FLOPS with its source")


def fetch_scalar(x):
    """Fetch one device scalar to host — a synchronization point (the
    bytes prove completion)."""
    return float(np.asarray(jax.device_get(x)).ravel()[0])


def measure_per_step(run_steps, k=10, repeats=2, fetch=fetch_scalar):
    """Steady-state seconds/step of a step function, by differencing.

    ``run_steps(n)`` must execute ``n`` *dependent* steps (state threaded
    through, so none can be elided) and return a device scalar handle from
    the final step.  Returns ``(per_step_s, diagnostics)`` where
    diagnostics records the raw T(K)/T(2K) minima and whether the
    differencing had to fall back to the naive upper bound.
    """
    if k < 1:
        raise ValueError("k must be >= 1")
    t_k = t_2k = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fetch(run_steps(k))
        t_k = min(t_k, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fetch(run_steps(2 * k))
        t_2k = min(t_2k, time.perf_counter() - t0)
    per_step = (t_2k - t_k) / k
    fallback = per_step <= 0
    if fallback:
        # noise swamped the difference (steps far cheaper than the jitter of
        # a window's fixed cost): the naive bound still contains that cost
        # once, so flag it as an upper bound
        per_step = t_2k / (2 * k)
    return per_step, {
        "t_k_s": t_k, "t_2k_s": t_2k, "k": k,
        "naive_fallback": fallback,
    }
