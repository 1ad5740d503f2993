"""Prove the fused-normalization lever with the real TPU compiler, no chip.

The F008 (memory-bound) remediation's claim: ResNet's batch norm costs
three HBM round-trips of the activation under XLA's lowering — a
statistics pass reading ``x``, then a normalize/scale-bias pass reading
``x`` again and writing ``y`` (plus the residual/activation epilogue) —
while the fused Pallas kernel (``ops/pallas/fused_norm.py``) does the
whole thing in ONE VMEM pass: one activation read, one result write.

This tool makes the claim compile-time evidence:

  1. **Mosaic lowerability** — ``fused_batch_norm`` (and the GroupNorm
     variant) AOT-compile for the deviceless v5e topology through the
     REAL Mosaic/XLA:TPU pipeline (``tpu_custom_call`` asserted
     present, so the XLA fallback can never masquerade as kernel
     validation).
  2. **The norm-site byte delta** — XLA:TPU's own ``cost_analysis`` of
     the two executables: the fused kernel accesses >= 30% fewer HBM
     bytes than the unfused reference lowering at the same norm site
     (the acceptance bar), and its roofline time
     (``cost_model.roofline_s``) is no worse.

Compile-time evidence, honestly labeled — RELATIVE effect on the
emitted norm-site program, not an on-chip measurement.  Writes
``records/v5e_aot/fused_norm_lever.json``.  Run: ``make aot-fused-norm``.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# deviceless topology construction must not wait on a GCE metadata
# server that off-GCE hosts cannot answer (hangs otherwise)
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402

TOPOLOGY = os.environ.get("MOSAIC_AOT_TOPOLOGY", "v5e:2x2")
# a late-ResNet-50 norm site: (B=8, 16, 16, 256) bf16 activations —
# 2048 rows x 256 channels, exactly two lane blocks, slab fits VMEM
ROWS = 2048
CHANNELS = 256
DTYPE = jnp.bfloat16
# the acceptance bar: the fused kernel must access at least this
# fraction fewer XLA-counted HBM bytes than the unfused lowering
MIN_BYTES_REMOVED_FRAC = 0.30


def main():
    import tools.mosaic_aot_check as mac
    from tools.mosaic_aot_check import _git_sha, _xla_stats

    from autodist_tpu.ops.pallas.fused_norm import (batch_norm_reference,
                                                    fused_batch_norm,
                                                    fused_group_norm)
    from autodist_tpu.simulator.cost_model import (DEFAULT_HBM_GBPS,
                                                   DEFAULT_MXU_EFF,
                                                   DEFAULT_PEAK_FLOPS,
                                                   roofline_s)

    os.environ.setdefault("AUTODIST_IS_TESTING", "True")
    mac.TOPO = topologies.get_topology_desc(TOPOLOGY, "tpu")

    def _roofline_us(stats):
        return 1e6 * roofline_s(
            stats.get("xla_flops", 0.0), stats.get("xla_bytes_accessed", 0.0),
            peak_flops=DEFAULT_PEAK_FLOPS * DEFAULT_MXU_EFF,
            hbm_gbps=DEFAULT_HBM_GBPS)

    x_aval = jax.ShapeDtypeStruct((ROWS, CHANNELS), DTYPE)
    v_aval = jax.ShapeDtypeStruct((CHANNELS,), jnp.float32)

    t0 = time.time()
    # the fused norm site: stats + normalize + scale-bias + residual +
    # relu in one VMEM pass (the exact epilogue a ResNet block ends with)
    exe_fused, _ = mac._compile(
        lambda x, s, b, r: fused_batch_norm(
            x, s, b, act="relu", residual=r, interpret=False),
        x_aval, v_aval, v_aval, x_aval)
    fused = _xla_stats(exe_fused)

    # the lowering it replaces: the unfused reference as XLA emits it —
    # a stats pass over x, then the normalize/epilogue pass re-reading x
    exe_ref, _ = mac._compile(
        lambda x, s, b, r: batch_norm_reference(
            x, s, b, act="relu", residual=r),
        x_aval, v_aval, v_aval, x_aval, expect_mosaic=False)
    ref = _xla_stats(exe_ref)

    # the tpu_custom_call body is OPAQUE to XLA's cost_analysis (it
    # counted ~23 KB for a 3 MB-operand kernel), so floor the fused
    # side at one read per argument byte + one write per output byte —
    # exactly the single-VMEM-pass kernel's true HBM traffic.  The
    # comparison stays conservative: the floor can only overstate the
    # fused side, never the reference's XLA-counted total.
    fused["hbm_bytes_floor"] = max(
        fused["xla_bytes_accessed"],
        fused["argument_size_in_bytes"] + fused["output_size_in_bytes"])
    fused_floored = dict(fused, xla_bytes_accessed=fused["hbm_bytes_floor"])
    fused_us, ref_us = _roofline_us(fused_floored), _roofline_us(ref)
    removed = ref["xla_bytes_accessed"] - fused["hbm_bytes_floor"]
    frac = removed / ref["xla_bytes_accessed"] if \
        ref["xla_bytes_accessed"] else 0.0
    assert frac >= MIN_BYTES_REMOVED_FRAC, (
        f"fused norm must remove >= {MIN_BYTES_REMOVED_FRAC:.0%} of the "
        f"norm-site HBM bytes, got {frac:.1%}", fused, ref)
    assert fused_us <= ref_us + 1e-9, (fused_us, ref_us)

    # the GroupNorm variant must also be Mosaic-lowerable (batch of 8
    # samples, 32 groups — the ResNet norm="gn" knob's configuration)
    gn_aval = jax.ShapeDtypeStruct((8, ROWS // 8, CHANNELS), DTYPE)
    gn = {"mosaic_compiles": False}
    try:
        exe_gn, _ = mac._compile(
            lambda x, s, b: fused_group_norm(x, s, b, 32, interpret=False),
            gn_aval, v_aval, v_aval)
        gn = {"mosaic_compiles": True, **_xla_stats(exe_gn)}
    except Exception as e:  # noqa: BLE001 — recorded honestly, not hidden
        gn["error"] = f"{type(e).__name__}: {e}"[:300]

    out_dir = os.environ.get("AOT_SWEEP_DIR") or os.path.join(
        REPO, "records", "v5e_aot")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "fused_norm_lever.json")
    record = {
        "topology": TOPOLOGY,
        "norm_site": {"rows": ROWS, "channels": CHANNELS,
                      "dtype": "bf16", "epilogue": "residual+relu",
                      "activation_mb": round(
                          ROWS * CHANNELS * 2 / 2 ** 20, 2)},
        "method": (
            "deviceless XLA:TPU compile of the fused Pallas batch norm "
            "(one VMEM pass) vs the unfused reference lowering (stats "
            "pass + normalize/epilogue pass) at the same norm site; "
            "the custom-call body is opaque to XLA cost_analysis, so "
            "the fused side is FLOORED at argument+output bytes (one "
            "read per operand, one write per result — the kernel's true "
            "single-pass traffic); roofline pred = cost_model.roofline_s "
            "on the counters; RELATIVE compile-time evidence, not an "
            "on-chip measurement"),
        "fused_kernel": {**fused, "roofline_us": round(fused_us, 2)},
        "unfused_reference": {**ref, "roofline_us": round(ref_us, 2)},
        "hbm_bytes_removed": round(removed),
        "hbm_bytes_removed_frac": round(frac, 4),
        "roofline_speedup": round(ref_us / fused_us, 3) if fused_us else None,
        "group_norm_variant": gn,
        "compile_seconds": round(time.time() - t0, 1),
        "git_sha": _git_sha(),
        "recorded_unix": int(time.time()),
    }
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"[aot-fused-norm] fused {fused_us:.1f}us vs unfused "
          f"{ref_us:.1f}us ({record['hbm_bytes_removed']} HBM bytes "
          f"removed, {frac:.1%})")
    print(f"[aot-fused-norm] wrote {out}")


if __name__ == "__main__":
    main()
