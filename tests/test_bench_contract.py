"""What bench.py promises: it measures on a TPU in its own process or it
fails.  No record without a TPU, no peak for a device the table does not
know, and the CPU proxy modes run only when named and say they are CPU."""
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
from autodist_tpu.utils import timing  # noqa: E402


def _run_bench(*argv, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *argv],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_record():
    proc = _run_bench()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""          # no record of any kind
    assert "no TPU" in proc.stderr


def test_unknown_model_exits_nonzero_without_a_record():
    proc = _run_bench(BENCH_MODEL="resnet5000")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_bench_fails_in_process_before_building_anything(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("a session was built without a TPU")

    monkeypatch.setattr(bench, "_build_resnet", boom)
    monkeypatch.setattr(bench, "_build_gpt", boom)
    with pytest.raises(SystemExit, match="no TPU"):
        bench._bench()


def test_measuring_starts_no_child_process():
    # one process owns the chip: the file neither imports subprocess nor
    # re-enters itself through mode variables in the environment
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "subprocess" not in src and "os.exec" not in src
    assert not hasattr(bench, "subprocess")


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5p", 459e12),
    ("TPU v5", 459e12), ("TPU v4", 275e12), ("TPU v6 lite", 918e12)])
def test_peak_flops_table_hit(kind, peak):
    assert timing.peak_flops(types.SimpleNamespace(device_kind=kind)) == peak


@pytest.mark.parametrize("kind", ["cpu", "", "NVIDIA T4", "TPU v7x"])
def test_peak_flops_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no bf16 peak"):
        timing.peak_flops(types.SimpleNamespace(device_kind=kind))
    assert not hasattr(timing, "DEFAULT_PEAK_BF16")


@pytest.mark.parametrize("flag,fn", [("--cpu-proxy", "_cpu_proxy"),
                                     ("--serve-proxy", "_serve_proxy")])
def test_proxy_runs_only_when_named(monkeypatch, capsys, flag, fn):
    called = []

    def fake(name):
        def run():
            called.append(name)
            return {"metric": name, "backend": "cpu"}
        return run

    for name in ("_bench", "_cpu_proxy", "_serve_proxy"):
        monkeypatch.setattr(bench, name, fake(name))
    bench.main([flag])
    assert called == [fn]
    assert json.loads(capsys.readouterr().out)["backend"] == "cpu"
    called.clear()
    bench.main([])
    assert called == ["_bench"]      # never a proxy because no mode was named


def test_proxy_modes_exclude_each_other():
    with pytest.raises(SystemExit):
        bench.main(["--cpu-proxy", "--serve-proxy"])


def test_serve_proxy_labels_itself_cpu():
    rec = bench._serve_proxy()
    assert rec["metric"] == bench.SERVE_PROXY_METRIC
    assert rec["backend"] == "cpu"
    assert "CPU-mesh" in rec["note"]
