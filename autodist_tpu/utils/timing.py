"""Step timing under asynchronous dispatch: a window closed by a host fetch.

JAX dispatch is async: a timed window has to end at a point where the
device has really finished.  ``seconds_per_step`` runs K *dependent* steps
(each consuming the previous state, so the device cannot reorder or elide
them) and closes the window with a host fetch of one device scalar from the
last of them: the bytes cannot arrive before the program producing them
finishes.  What the window costs once (the fetch, the dispatch ramp) is
spread over K steps; on the chip it read within 0.2-0.6 % of the step
(PERF.md, PR 21).

This is a timer for tools and smoke checks.  A training speed is stated by
``benchmark/`` alone (``BENCHMARK.json``, ``PERF.md``, the ledger).
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


def seconds_per_step(run_steps, k):
    """Seconds per step over one window of ``k`` dependent steps.

    ``run_steps(k)`` must execute ``k`` *dependent* steps (state threaded
    through, so none can be elided) and return a device scalar handle from
    the final step; fetching it closes the window.
    """
    if k < 1:
        raise ValueError("k must be >= 1")
    t0 = time.perf_counter()
    fetch_scalar(run_steps(k))
    return (time.perf_counter() - t0) / k
