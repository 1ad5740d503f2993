"""State-space mixer: milliseconds per step on device 0 of what surrounds
the scan, the operations under the model's ``ssd.proj`` scope: the
in-projection, the convolution with its SiLU, the steps' softplus, the gated
group norm and the out-projection."""
from benchmark.harness import ssd_scopes


def read(run):
    return ssd_scopes.scope_ms(run, "ssd.proj")
