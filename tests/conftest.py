"""Test configuration.

Forces an 8-device virtual CPU platform (SURVEY.md section 4: the analog of
the reference's two-local-tf.Server rig) BEFORE jax is imported anywhere, so
multi-chip sharding is exercised without TPU hardware.  Also mirrors the
reference's ``--run-integration`` gate (reference tests/conftest.py:4-16).
"""
import os

os.environ.setdefault("AUTODIST_IS_TESTING", "True")

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# The chip tool copies the tree as it stands: a test run must not leave this
# machine's CPU executables in the in-checkout compile cache
# (autodist_tpu/utils/compile_cache.py) for another machine to load.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_addoption(parser):
    parser.addoption(
        "--run-integration",
        action="store_true",
        default=False,
        help="run integration tests (slow, full end-to-end)",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-integration"):
        return
    skip = pytest.mark.skip(reason="need --run-integration option to run")
    for item in items:
        if "integration" in item.keywords:
            item.add_marker(skip)
