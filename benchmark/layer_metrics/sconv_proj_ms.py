"""Short-convolution mixer: milliseconds per step on device 0 of the mixers'
two projections, the operations under the model's ``sconv.proj`` scope: the
in-projection's three products and the out-projection (forward, recomputed
and backward; self time from the device trace)."""
from benchmark.harness import lfm2_scopes


def read(run):
    return lfm2_scopes.scope_ms(run, "sconv.proj")
