"""Test env: force JAX onto 8 virtual CPU devices before jax is imported.

SURVEY.md §4: multi-chip paths are tested with
``--xla_force_host_platform_device_count`` virtual devices rather than real
slices; the accelerator-free kernel tests don't touch JAX at all.
"""

import os
import tempfile

# Flight-recorder crash dumps (obs/flight.py) default to the working
# directory; the suite's deliberate poison-batch tests must not litter
# the repo root (tests that assert on dumps monkeypatch their own dir).
os.environ.setdefault(
    "TPU_LLM_CRASH_DIR", tempfile.mkdtemp(prefix="flight_crash_test_")
)

# Force (not setdefault): tests run on virtual CPU devices even on a host
# whose environment points JAX at a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# XLA:CPU compiles each distinct op shape at ~0.5-1 s in this environment;
# a warm persistent cache cuts the suite from minutes to seconds. JAX
# reads JAX_COMPILATION_CACHE_DIR itself; where the variable is unset the
# CPU suite's cache goes OUTSIDE the checkout (it holds thousands of
# files, and the chip tool copies the tree). Set through the environment
# so enable_compilation_cache() and subprocess children see the same
# directory and set no other.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")

import jax  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
# This environment's default matmul precision truncates f32 matmul inputs to
# bf16 (observed ~6e-3 abs error on unit-scale data), which would drown the
# numerical parity tests; force full f32 for tests only.
jax.config.update("jax_default_matmul_precision", "highest")
