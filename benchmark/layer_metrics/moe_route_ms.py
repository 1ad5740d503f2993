"""Expert layer: milliseconds per step of routing on device 0, the
operations under ``moe.route``: the router's scores, the ten largest, the
packing of the assignments to held experts and the weighted sum back onto
the tokens."""
from benchmark.harness import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "moe.route")
