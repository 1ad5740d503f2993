"""State-space mixer: milliseconds per step of the selective scan on device
0, the operations under the model's ``ssd.scan`` scope (forward, recomputed
and backward; self time from the device trace)."""
from benchmark.harness import ssd_scopes


def read(run):
    return ssd_scopes.scope_ms(run, "ssd.scan")
