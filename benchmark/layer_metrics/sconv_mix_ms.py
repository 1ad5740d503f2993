"""Short-convolution mixer: milliseconds per step on device 0 of the mix
itself, the operations under the model's ``sconv.mix`` scope: ``B * u``, the
taps of the causal depthwise convolution and ``C * c`` (forward, recomputed
and backward; self time from the device trace)."""
from benchmark.harness import lfm2_scopes


def read(run):
    return lfm2_scopes.scope_ms(run, "sconv.mix")
