"""Test config: force an 8-device virtual CPU mesh.

The reference simulates multi-node with multi-process localhost
(reference: python/paddle/fluid/tests/unittests/test_collective_base.py:162);
on TPU we improve on that with XLA's host-platform device simulation —
every test sees 8 virtual devices, so mesh/sharding tests run without
real chips (SURVEY.md §4 lesson).
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# The persistent compile cache defaults to a fixed directory INSIDE the
# checkout (framework/compile_cache.py); the suite must not grow the
# checkout (the chip tool copies the tree as it stands), so it places
# the cache from outside, the way a deployment would — at a FIXED path
# under the system temp dir (the path is part of the cache key), which
# also lets a second run of the suite start warm.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import tempfile
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        tempfile.gettempdir(), "paddle_tpu_test_jax_cache")

# Tests compare against float64 NumPy references: force exact f32 matmuls.
# (Production on TPU keeps the default fast MXU path.)
import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
# The suite never touches a chip, whatever JAX_PLATFORMS the caller
# exported (a chip belongs to one process; tests are not that process).
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 command "
        "(-m 'not slow'); tools/run_tier1.sh runs these as extra passes")


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """A test that installs a global mesh must not leak it into the next
    test: eager ops consult the mesh (constrain_dim lays values out
    SPMD), so a stale 8-device mesh changes single-device numerics —
    an ordering-dependent flake (surfaced by running test_pipeline
    before test_llama)."""
    yield
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh_mod.set_mesh(None)
