"""``utils/timing``: the peaks table (longest prefix wins, an unknown chip is
an error) and the one timer, a window of K dependent steps closed by a host
fetch of the last step's handle."""
import types

import numpy as np
import pytest

from autodist_tpu.utils import timing


@pytest.mark.parametrize("kind,peak", [
    ("TPU v2", 46e12), ("TPU v3", 123e12), ("TPU v4", 275e12),
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5p", 459e12),
    ("TPU v5", 459e12), ("TPU v6 lite", 918e12), ("TPU v6e", 918e12)])
def test_peak_flops_table_hit(kind, peak):
    assert timing.peak_flops(types.SimpleNamespace(device_kind=kind)) == peak


@pytest.mark.parametrize("kind", ["cpu", "", "NVIDIA T4", "TPU v7x"])
def test_peak_flops_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no bf16 peak"):
        timing.peak_flops(types.SimpleNamespace(device_kind=kind))
    assert not hasattr(timing, "DEFAULT_PEAK_BF16")


def test_seconds_per_step_runs_k_steps_and_fetches_the_last_handle(
        monkeypatch):
    ran, fetched = [], []

    def run_steps(n):
        for _ in range(n):
            ran.append(np.float32(len(ran)))
        return ran[-1]

    def fetch(handle):
        fetched.append(handle)
        return float(handle)

    monkeypatch.setattr(timing, "fetch_scalar", fetch)
    dt = timing.seconds_per_step(run_steps, 5)
    assert len(ran) == 5              # exactly k steps, no second window
    assert len(fetched) == 1 and fetched[0] is ran[-1]
    assert dt > 0


def test_seconds_per_step_refuses_an_empty_window():
    with pytest.raises(ValueError, match="k must be >= 1"):
        timing.seconds_per_step(lambda n: np.float32(0), 0)
