"""Pallas kernels: milliseconds per step in the flash attention kernels on
device 0 (forward, recomputed forward, dq and dk/dv), summed from the device
trace.  The kernels are the custom calls to ``tpu_custom_call`` whose
instruction the compiler named ``attn`` after the module they sit in."""
from benchmark.harness import trace


def flash_calls(run):
    s = run["summary"]
    if not s:
        return []
    lo, hi = s["window"]
    return [c for c in trace.kernel_calls(run["lanes"], s["planes"][0], lo, hi)
            if trace.stem(c[0]) == "attn"]


def read(run):
    calls = flash_calls(run)
    if not calls:
        return None
    return 1e3 * sum(c[1] for c in calls) / run["summary"]["steps"]
