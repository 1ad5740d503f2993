"""Compile for a TPU pod before the pod exists.

``AutoDist.aot_compile()`` builds the distributed training step exactly
as ``distribute()`` would and compiles it through the real Mosaic/
XLA:TPU toolchain against a DEVICELESS topology description: compile
errors, HBM fit, and XLA's cost analysis for the target generation —
plus a serializable executable — with zero chips attached.

Run (plain CPU process, no TPU plugin):
    python examples/aot_precompile.py [topology]   # default v5e:2x2
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import optax

from autodist_tpu.autodist import AutoDist
from autodist_tpu.models import train_lib
from autodist_tpu.models.gpt import GPTConfig
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import Parallax


def main():
    topology = sys.argv[1] if len(sys.argv) > 1 else "v5e:2x2"
    os.environ.setdefault("AUTODIST_IS_TESTING", "True")

    S, B = 128, 8
    cfg = GPTConfig(vocab_size=2048, hidden_size=128, num_layers=2,
                    num_heads=2, intermediate_size=512, max_position=S,
                    dropout_rate=0.0, dtype=jnp.bfloat16,
                    attention_impl="auto")
    loss_fn, params, sparse = train_lib.gpt_capture(
        cfg, S, streaming_loss=True)

    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(4),
                  strategy_builder=Parallax())
    aot = ad.aot_compile(loss_fn, params, optax.adamw(1e-3),
                         batch_shapes={"tokens": ((B, S), jnp.int32),
                                       "targets": ((B, S), jnp.int32)},
                         topology=topology, sparse_vars=sparse,
                         has_rng=True)

    m = aot.memory_analysis
    flops = float(aot.cost_analysis.get("flops", 0.0))
    print(f"target      : {aot.n_devices} x {aot.device_kind} ({topology})")
    print(f"fits HBM    : {aot.fits_hbm()} "
          f"(args {m['argument_size_in_bytes'] / 1e6:.0f} MB + temps "
          f"{m['temp_size_in_bytes'] / 1e6:.0f} MB per device)")
    print(f"XLA flops   : {flops / 1e9:.1f} GFLOP per step per device")
    blob = aot.serialize()
    print(f"executable  : {len(blob) / 1e6:.1f} MB serialized "
          f"(compile-once-deploy-many)")
    mosaic = "tpu_custom_call" in aot.as_hlo_text()
    print(f"flash kernel: {'Mosaic-compiled' if mosaic else 'XLA fallback'}")
    assert mosaic, "expected the Pallas flash kernel in the program"


if __name__ == "__main__":
    main()
