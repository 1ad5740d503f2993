"""Four virtual CPU devices, set before jax is imported anywhere; the
in-checkout compile cache stays off so that no CPU executable is left for a
machine with a chip to find."""
import os
import sys

os.environ.setdefault("AUTODIST_IS_TESTING", "True")
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
