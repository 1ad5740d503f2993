"""Prove the EQuARX fused-hop lever with the real TPU compiler, no chip.

The ``equarx_int8`` codec's claim (arXiv 2506.17615): the quantized
allreduce's hop — dequantize the received peer chunks, mean, REquantize
— runs as ONE Pallas VMEM pass (``ops.pallas.quantize.equarx_hop``), so
the full-precision accumulator never round-trips through HBM between
the all_to_all and the all_gather.  The wire bytes are identical to the
unfused :class:`Int8Compressor` (same ``wire_byte_factor``); the win is
entirely the removed intermediate f32 buffer + kernel launch on the hop.

This tool makes both halves of that claim compile-time evidence:

  1. **Mosaic lowerability** — the fused hop AOT-compiles for the
     deviceless v5e topology through the REAL Mosaic/XLA:TPU pipeline
     (``tpu_custom_call`` asserted present, so the XLA fallback can
     never masquerade as kernel validation), alongside the unfused
     two-kernel pattern (dequant-sum -> HBM -> requantize) it replaces.
  2. **The hop-level delta** — XLA:TPU's own ``cost_analysis`` of the
     two executables: the fused hop accesses strictly fewer HBM bytes,
     and its roofline time ``max(flops/(peak*eff), bytes/hbm_bw)`` is
     no worse than the separate pattern's.
  3. **DCN-bottleneck context** — the cost model's step estimates on a
     bandwidth-starved two-node spec: the equarx schedule prices the
     same DCN wire as int8 (the factor IS shared) and strictly beats
     the uncompressed flat ring, which is why schedule_search may pick
     it on slow DCN hops.

Compile-time evidence, honestly labeled — RELATIVE effect on the
emitted hop program, not an on-chip measurement.  Writes
``records/v5e_aot/equarx_lever.json``.  Run: ``make aot-equarx``.
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
PEAK_FLOPS = 394e12
MXU_EFF = 0.45
HBM_BW = 819e9
# hop geometry: D peer chunks of N quantization blocks — a ~8.4 MB f32
# accumulator, big enough that the HBM round-trip dominates the delta
D_PEERS = 4
N_BLOCKS = 8192


def _roofline_us(stats):
    flops = stats.get("xla_flops", 0.0)
    bytes_ = stats.get("xla_bytes_accessed", 0.0)
    return 1e6 * max(flops / (PEAK_FLOPS * MXU_EFF), bytes_ / HBM_BW)


def main():
    import tools.mosaic_aot_check as mac
    from tools.mosaic_aot_check import _git_sha, _xla_stats

    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.ops.pallas.quantize import (BLOCK, dequant_sum,
                                                  equarx_hop, quantize_int8)
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.simulator.cost_model import estimate
    from autodist_tpu.strategy import AllReduce

    os.environ.setdefault("AUTODIST_IS_TESTING", "True")
    mac.TOPO = topologies.get_topology_desc(TOPOLOGY, "tpu")

    q_aval = jax.ShapeDtypeStruct((D_PEERS, N_BLOCKS, BLOCK), jnp.int8)
    s_aval = jax.ShapeDtypeStruct((D_PEERS, N_BLOCKS, 1), jnp.float32)

    t0 = time.time()
    # the fused hop: dequant + peer-mean + requant in one VMEM pass
    exe_fused, _ = mac._compile(
        lambda q, s: equarx_hop(q, s, D_PEERS), q_aval, s_aval)
    fused = _xla_stats(exe_fused)

    # the pattern it replaces: dequant-sum kernel -> f32 accumulator in
    # HBM -> block-requantize kernel
    def separate(q, s):
        acc = dequant_sum(q, s) / D_PEERS
        return quantize_int8(acc)

    exe_sep, _ = mac._compile(separate, q_aval, s_aval)
    sep = _xla_stats(exe_sep)

    fused_us, sep_us = _roofline_us(fused), _roofline_us(sep)
    assert fused["xla_bytes_accessed"] < sep["xla_bytes_accessed"], (
        "the fused hop must remove HBM traffic", fused, sep)
    assert fused_us <= sep_us + 1e-9, (fused_us, sep_us)

    # DCN-bottleneck context: a bandwidth-starved two-node spec where the
    # slow wire dominates the step — the regime the codec targets
    spec = ResourceSpec(resource_info={"nodes": [
        {"address": "10.0.0.1", "chips": [0, 1, 2, 3], "chief": True,
         "network_bandwidth": 10},
        {"address": "10.0.0.2", "chips": [0, 1, 2, 3],
         "network_bandwidth": 10}]})
    item = ModelItem(lambda p, b: 0.0, {"w": jnp.zeros((2048, 2048))})
    ests = {}
    for label, builder in (
            ("flat_none", AllReduce()),
            ("two_level_int8", AllReduce(hierarchy="two_level",
                                         dcn_compressor="Int8Compressor")),
            ("two_level_equarx", AllReduce(hierarchy="two_level",
                                           dcn_compressor="equarx_int8"))):
        est = estimate(builder.build(item, spec), item, spec,
                       flops_per_example=1e9)
        ests[label] = {"total_s": round(est.total_s, 6),
                       "hier_dcn_bytes": est.breakdown.get("hier_dcn_bytes"),
                       "comm_s": round(est.comm_s, 6)}
    # same wire as int8 (the factor is shared); beats the flat ring
    assert ests["two_level_equarx"]["total_s"] == \
        ests["two_level_int8"]["total_s"]
    assert ests["two_level_equarx"]["total_s"] < ests["flat_none"]["total_s"]

    out_dir = os.environ.get("AOT_SWEEP_DIR") or os.path.join(
        REPO, "records", "v5e_aot")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "equarx_lever.json")
    record = {
        "topology": TOPOLOGY,
        "hop_geometry": {"peers": D_PEERS, "blocks": N_BLOCKS,
                         "block": BLOCK,
                         "accumulator_mb": round(
                             N_BLOCKS * BLOCK * 4 / 2 ** 20, 2)},
        "method": (
            "deviceless XLA:TPU compile of the fused equarx_hop vs the "
            "separate dequant-sum -> HBM -> requantize pattern; roofline "
            "pred = max(flops/(peak*mxu_eff), bytes/hbm_bw); RELATIVE "
            "compile-time evidence, not an on-chip measurement"),
        "fused_hop": {**fused, "roofline_us": round(fused_us, 2)},
        "separate_pattern": {**sep, "roofline_us": round(sep_us, 2)},
        "hbm_bytes_removed": round(
            sep["xla_bytes_accessed"] - fused["xla_bytes_accessed"]),
        "roofline_speedup": round(sep_us / fused_us, 3) if fused_us else None,
        "dcn_bottleneck_step_estimates": {
            "note": ("cost-model step totals on a 10 Gbps two-node spec: "
                     "equarx prices the int8 wire exactly (shared "
                     "wire_byte_factor) and beats the uncompressed flat "
                     "ring; the fused-hop delta above is ON TOP of this"),
            **ests},
        "compile_seconds": round(time.time() - t0, 1),
        "git_sha": _git_sha(),
        "recorded_unix": int(time.time()),
    }
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"[aot-equarx] fused {fused_us:.1f}us vs separate {sep_us:.1f}us "
          f"({record['hbm_bytes_removed']} HBM bytes removed)")
    print(f"[aot-equarx] wrote {out}")


if __name__ == "__main__":
    main()
