"""Gated DeltaNet: milliseconds per step round the rule on device 0, the
operations under ``gdn.proj``: the projections in and out, the short
convolution, the normalisations and the gates."""
from benchmark.harness import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "gdn.proj")
