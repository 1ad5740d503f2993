"""Short-convolution mixer: the least time the chip could take for a step's
mixes (one forward and one backward pass of each short-convolution layer,
the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
shapes: ``lfm2_cost.short_conv_cost``, bytes-bound) over the time under
``sconv.mix``, in percent.  Work run again under recomputation adds to the
time only."""
from benchmark.harness import lfm2_scopes
from benchmark.harness.flops import roofline_least_seconds
from benchmark.harness.lfm2_cost import short_conv_cost


def read(run):
    ms = lfm2_scopes.scope_ms(run, "sconv.mix")
    sh = run.get("shapes") or {}
    if not ms or not run.get("peaks") or "sconv_layers" not in sh:
        return None
    tokens = sh["batch_per_chip"] * sh["seq_len"]
    least = sum(
        roofline_least_seconds(*short_conv_cost(
            kind, tokens, sh["sconv_channels"], sh["sconv_taps"]),
            run["peaks"])[0]
        for kind in ("fwd", "bwd"))
    return 100.0 * sh["sconv_layers"] * least / (1e-3 * ms)
