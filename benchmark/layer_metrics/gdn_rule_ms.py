"""Gated DeltaNet: milliseconds per step of the delta rule on device 0, the
operations under the model's ``gdn.rule`` scope (forward, recomputed and
backward; self time from the device trace)."""
from benchmark.harness import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "gdn.rule")
