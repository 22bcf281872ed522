"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip sharding (shard_map / GSPMD specs over a Mesh) is tested on 8
virtual CPU devices; the chip itself is exercised by chip_smoke.py, not by
the suite.  Must run before jax initializes its backends, hence env vars
here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Persistent compilation cache: the suite compiles ~40 engine topologies at
# ~15 s each; caching them across runs cuts the suite from ~10 min to ~2.
# JAX_COMPILATION_CACHE_DIR, when set, places it; otherwise it lives at a
# fixed path next to the tests.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(__file__), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import graphite_tpu  # noqa: E402,F401  (enables x64)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy variants excluded from the tier-1 run (-m 'not slow')")
