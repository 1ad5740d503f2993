"""Public AOT API: ``AutoDist.aot_compile()`` compiles the distributed
step for a deviceless v5e topology through the real TPU toolchain and
reports capacity/cost — driven exactly as a user would, in a subprocess
whose env is scrubbed of the interactive TPU plugin."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.integration

SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, %(repo)r)
    import os
    os.environ["AUTODIST_IS_TESTING"] = "True"
    import jax, jax.numpy as jnp, numpy as np, optax
    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import Parallax

    r = np.random.RandomState(0)
    params = {"emb": jnp.asarray(r.randn(256, 32), jnp.float32),
              "w": jnp.asarray(r.randn(32, 8), jnp.float32)}

    def loss(p, b, rng):
        h = p["emb"][b["ids"]] @ p["w"]
        h = h + 0.01 * jax.random.normal(rng, h.shape)
        return jnp.mean(h ** 2)

    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(4),
                  strategy_builder=Parallax())
    aot = ad.aot_compile(loss, params, optax.adamw(1e-3),
                         batch_shapes={"ids": ((16,), jnp.int32)},
                         topology="v5e:2x2", sparse_vars=["emb"],
                         has_rng=True)
    assert aot.n_devices == 4
    assert "TPU" in aot.device_kind
    ca = aot.cost_analysis
    assert float(ca.get("flops", 0)) > 0
    ma = aot.memory_analysis
    assert ma["argument_size_in_bytes"] > 0
    assert aot.fits_hbm()
    assert "all-reduce" in aot.as_hlo_text() or (
        "reduce-scatter" in aot.as_hlo_text())
    blob = aot.serialize()
    assert isinstance(blob, bytes) and len(blob) > 1000
    print("AOT_API_OK", aot.device_kind, len(blob))
""")


def test_public_aot_compile_api(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = ""
    env.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"repo": repo}], env=env,
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "AOT_API_OK" in proc.stdout
