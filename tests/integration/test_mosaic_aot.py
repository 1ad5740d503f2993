"""The Pallas surface must compile through the REAL Mosaic/XLA:TPU
compiler (deviceless libtpu topology — tools/mosaic_aot_check.py).  Run
as a subprocess: the checker describes the topology in its own process,
which then holds the TPU library's lock."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.integration

TOOL = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                    "mosaic_aot_check.py")


def test_mosaic_aot_surface_compiles(tmp_path):
    out = tmp_path / "mosaic_aot.json"
    # write to tmp: a test run must never overwrite the committed
    # evidence artifact with a -dirty stamp
    env = dict(os.environ, MOSAIC_AOT_OUT=str(out))
    proc = subprocess.run([sys.executable, TOOL], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    with open(out) as f:
        doc = json.load(f)
    assert doc["ok"] is True
    assert set(doc["checks"]) == {
        "flash_attention_fwd", "flash_attention_bwd", "int8_quantize",
        "ring_attention_4dev", "entry_flagship_gpt",
        "engine_step_parallax_4dev", "gpt_train_step_flash_streaming_4dev",
        "multihost_subset_ps_16dev_4host", "wire_dtype_bf16_allreduce",
        "llama_gqa_train_step_4dev", "pipeline_1f1b_4dev",
        "gpt_decode_rollout_serving", "tensor_parallel_2x2",
        "expert_parallel_moe_2x2"}
    assert all(c["ok"] for c in doc["checks"].values())
