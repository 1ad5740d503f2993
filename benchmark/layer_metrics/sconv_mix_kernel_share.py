"""Short-convolution mixer: the share of ``sconv_mix_ms`` spent inside Pallas
kernels, in percent: the custom calls to ``tpu_custom_call`` under the
model's ``sconv.mix`` scope (found by ``harness/lfm2_scopes.py``, whose
operations carry the scope) over the self time of everything under that
scope.  What is left is the work round the kernels (the sum of the taps'
partial gradients).  Nothing where no kernel runs under the scope: the XLA
form, or no such scope at all."""
from benchmark.harness import lfm2_scopes, trace

SCOPE = "sconv.mix"


def kernel_seconds(run):
    """Seconds inside the steady window in the kernels under ``SCOPE``."""
    rec, s = lfm2_scopes.of(run), run.get("summary")
    if not rec or not s or not run.get("lanes"):
        return 0.0
    under = {(op[0], op[1]) for op in rec["ops"] if op[3] == SCOPE}
    lo, hi = s["window"]
    return sum(e[2] / 1e9
               for e in trace.events_of(run["lanes"], s["planes"][0],
                                        trace.OPS_LINE)
               if len(e) > 3 and e[3].endswith("-> tpu_custom_call")
               and (e[0], e[1]) in under and e[1] >= lo and e[1] + e[2] <= hi)


def read(run):
    kernels = kernel_seconds(run)
    ms = lfm2_scopes.scope_ms(run, SCOPE)
    if not kernels or not ms:
        return None
    return 100.0 * (1e3 * kernels / run["summary"]["steps"]) / ms
