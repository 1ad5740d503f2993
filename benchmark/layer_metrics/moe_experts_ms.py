"""Expert layer: milliseconds per step in the held experts' grouped matrix
products on device 0, the operations under ``moe.experts``."""
from benchmark.harness import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "moe.experts")
