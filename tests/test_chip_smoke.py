"""chip_smoke.py off the chip: the script itself must refuse to run here, and
its phases — functions of a model config, sizes and devices — run at a tiny
size on the virtual CPU mesh.  The steering is here, not in the script: it has
no size or platform option for a rehearsal to use."""
import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from autodist_tpu.autodist import AutoDist  # noqa: E402
from autodist_tpu.models import GPT_TINY  # noqa: E402
from autodist_tpu.models.resnet import ResNet, ResNetBlock  # noqa: E402
from autodist_tpu.resource_spec import ResourceSpec  # noqa: E402
from autodist_tpu.strategy import AllReduce  # noqa: E402
from autodist_tpu.utils import compile_cache  # noqa: E402

TINY = dataclasses.replace(GPT_TINY, remat=True, dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def events():
    return chip_smoke.CacheEvents()


def _one_chip_autodist():
    return AutoDist(resource_spec=ResourceSpec.from_num_chips(1),
                    strategy_builder=AllReduce())


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["default", "chips4"])
def test_script_stops_at_the_device_check_without_a_tpu(argv):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_gpt_train_phase_tiny(tmp_path, events):
    rec = chip_smoke.phase_gpt_train(
        _one_chip_autodist(), TINY, batch=8, seq_len=64, steps=8, timing_k=2,
        seed=0, out_dir=str(tmp_path), devices=jax.devices()[:1],
        events=events)
    assert rec["platform"] == "cpu" and rec["device_count"] == 1
    assert len(rec["losses"]) == 8 and rec["losses"][-1] < rec["losses"][0]
    assert len(rec["reference_losses"]) == 2
    # off the chip "auto" attention is XLA's: what main() would refuse
    assert rec["tpu_custom_call"] is False
    assert rec["timing"]["s_per_step"] > 0
    assert os.path.exists(tmp_path / "gpt_corpus.bin")


def test_gpt_train_phase_fails_on_a_loss_that_disagrees(tmp_path, events,
                                                        monkeypatch):
    monkeypatch.setattr(chip_smoke, "reference_losses",
                        lambda *a, **k: [1.0, 1.0])
    with pytest.raises(SystemExit, match="the reference has 1.0"):
        chip_smoke.phase_gpt_train(
            _one_chip_autodist(), TINY, batch=8, seq_len=64, steps=2,
            timing_k=1, seed=0, out_dir=str(tmp_path),
            devices=jax.devices()[:1], events=events)


@pytest.mark.parametrize("losses,why", [
    ([2.0, float("nan"), 1.0], "non-finite"), ([2.0, 2.5], "did not fall")])
def test_check_losses_refuses(losses, why):
    with pytest.raises(SystemExit, match=why):
        chip_smoke.check_losses("phase", losses)


def test_resnet_train_phase_tiny(events):
    model = ResNet(stage_sizes=[1, 1], block_cls=ResNetBlock, num_classes=10,
                   num_filters=8)
    rec = chip_smoke.phase_resnet_train(
        _one_chip_autodist(), model, image_size=32, num_classes=10, batch=16,
        steps=8, timing_k=2, seed=0, devices=jax.devices()[:1],
        events=events)
    assert len(rec["losses"]) == 8 and rec["losses"][-1] < rec["losses"][0]


def test_four_chip_phase_on_four_virtual_devices(tmp_path, events):
    devices = jax.devices()[:4]
    builder = chip_smoke.SwitchableBuilder(AllReduce())
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(4),
                  strategy_builder=builder)
    rec = chip_smoke.phase_data_parallel(
        ad, builder, TINY, batch=8, seq_len=64, steps=4, seed=0,
        out_dir=str(tmp_path), devices=devices, events=events)
    assert rec["device_count"] == 4
    assert rec["mesh"] == {"shape": {"replica": 4},
                           "device_ids": [d.id for d in devices]}
    plain, zero = (rec["variants"][k] for k in
                   ("replicated_update", "sharded_update"))
    # the sharding assertions held inside the phase; what they saw:
    assert plain["opt_state_sharded_leaves"] == 0
    assert zero["opt_state_sharded_leaves"] > 0
    assert zero["collectives"]["all-gather"] > 0
    assert plain["collectives"]["all-gather"] == 0
    for v in (plain, zero):
        assert len(v["losses"]) == 4 and v["losses"][-1] < v["losses"][0]


def test_four_chip_phase_refuses_a_mesh_of_other_devices(tmp_path, events):
    builder = chip_smoke.SwitchableBuilder(AllReduce())
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(2),
                  strategy_builder=builder)
    with pytest.raises(SystemExit, match="mesh does not hold"):
        chip_smoke.phase_data_parallel(
            ad, builder, TINY, batch=8, seq_len=64, steps=2, seed=0,
            out_dir=str(tmp_path), devices=jax.devices()[:4], events=events)


# ---------------------------------------------------- compile cache helper --

KEYING = [("jax_compilation_cache_include_metadata_in_key", True),
          ("jax_hlo_source_file_canonicalization_regex",
           "^" + re.escape(REPO + os.sep))]


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: seen.append(a))
    assert compile_cache.ensure_compile_cache() == "/some/dir"
    assert seen == KEYING          # no directory is set, the keying is


def test_compile_cache_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    monkeypatch.setattr(jax.config, "update", lambda *a: seen.append(a))
    first = compile_cache.ensure_compile_cache()
    assert first == compile_cache.ensure_compile_cache() \
        == os.path.join(REPO, ".jax_cache")
    assert seen == (KEYING + [("jax_compilation_cache_dir", first)]) * 2
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_autodist_init_places_the_cache(monkeypatch):
    calls = []
    monkeypatch.setattr("autodist_tpu.autodist.ensure_compile_cache",
                        lambda: calls.append(1))
    _one_chip_autodist()
    assert calls == [1]
