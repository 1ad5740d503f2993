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
    _flags += " --xla_force_host_platform_device_count=8"
# These tests check values, not the speed of the CPU's code, and much of
# their time was LLVM optimising it: without that and with the eager
# gradients of seven files jitted, the tests' own seconds fell from 1,198 to
# 851 (PR 29, this sandbox; ROADMAP D13).  ``tests/test_tpu_compile.py`` sets
# both flags back for the TPU's compiler.  Scripts the tests start inherit
# them with the environment.
if "xla_backend_optimization_level" not in _flags:
    _flags += (" --xla_backend_optimization_level=0"
               " --xla_llvm_disable_expensive_passes=true")
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# The chip tool copies the tree as it stands: a test run must not leave this
# machine's CPU executables in the in-checkout compile cache
# (autodist_tpu/utils/compile_cache.py) for another machine to load.
jax.config.update("jax_enable_compilation_cache", False)

# flax's ``Module.init`` runs eagerly unless its caller jits it: every
# initializer and every layer's forward is then compiled op by op, shape by
# shape (DenseNet-121's init alone: 59 s; over all files 329 of the tests'
# 1,527 s).  The suite runs it as the benchmark's families do
# (``benchmark/families/*.py:make_params``): in one jitted call, with the
# same keys and initializers.
import flax.linen as nn  # noqa: E402

_eager_init = nn.Module.init


def _init_in_one_jitted_call(self, rngs, *args, **kwargs):
    return jax.jit(lambda r, a: _eager_init(self, r, *a, **kwargs))(rngs, args)


nn.Module.init = _init_in_one_jitted_call


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
