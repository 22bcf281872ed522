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

# Persistent compilation cache.  Tier-1 is compile-bound.  What Step 0 of
# PR 47 found (CPU, the driver's command, six workers; PERF.md "Tier-1's
# cost"): from an empty cache 4,637 programs compiled, 538 of them of a
# second or more and written here (61% of the suite's 7,393 s of case
# time is compiling, 26% tracing and lowering), 1,255-1,371 s of wall;
# from a warm cache 633 s (920-1,010 s and 437 s later that night, the
# shared machine quiet).  Only 93 of 629 constructions of such a program
# repeat one (a trace's content is part of its executable), so the suite
# costs what its programs cost.  The cache key follows the cache
# DIRECTORY's path (one tree under two directories shares no key; two
# checkouts under one directory share them all), the driver's checkout
# comes without `tests/.jax_cache`, and a PR that registers a scope
# (`obs/scopes.py: CACHE_TAG`) or touches the engine re-keys every
# program: the cold number is the one to plan on (ROADMAP D14).
# JAX_COMPILATION_CACHE_DIR, when set, places the cache; otherwise it
# lives at a fixed path next to the tests.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(__file__), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import graphite_tpu  # noqa: E402,F401  (enables x64)


# The order hook.  xdist's `--dist load` hands each worker RUNS of
# consecutive cases: a first run of a 24th of the suite each, then a 12th
# of what is left whenever a worker runs dry.  In file-name order the
# dense files of the end of the alphabet (`test_sweep.py`,
# `test_victim_lookup_golden.py`) fall into the last, shortest runs, and
# in some runs one worker then ends two or three minutes after the others
# (the driver's run of PR 46: 1,425 s of wall for 1,245 s of work a
# worker; here 22-145 s over a worker's share in six cold runs); cases
# sorted longest-first would put the 54 longest, 3,000 s, on one worker.
# So files stay whole, the dense files below come one after another with
# an equal share of the other files' cases after each, and the last runs
# are of cheap cases: 36-44 s over a worker's share in the three cold
# runs made in turn with three of the other order, and the shorter wall
# in each pair (PERF.md "Tier-1's cost").  A tuple of names, not of
# seconds: a new dense file is added by hand, and one left out costs what
# it cost before.
DENSE = (
    ("test_chip_compile.py", "TPU compiles for a described v5e:2x2; the"
     " persistent cache is off for them by design, so first"),
    ("test_memory_net.py", "one engine program a case"),
    ("test_sweep.py", "B-wide campaign programs + sequential runs"),
    ("test_shl2_memstress_golden.py", "256/1024-tile golden runs"),
    ("test_quantum_exit.py", "staged/sharded/vmapped program pairs"),
    ("test_memstress1024_golden.py", "64-tile golden interpreter"),
    ("test_directory_schemes.py", "a program a scheme"),
    ("test_mesh2d.py", "2D campaigns + solo references"),
    ("test_victim_lookup_golden.py", "served batch vs golden a case"),
    ("test_sharding.py", "sharded + single-device program pairs"),
    ("test_mosi.py", "a program a case"),
    ("test_memory_golden.py", "a program + a golden run a case"),
)


def pytest_collection_modifyitems(config, items):
    by_file = {}
    for item in items:
        by_file.setdefault(os.path.basename(str(item.fspath)), []).append(item)
    dense = [f for f, _ in DENSE if f in by_file]
    rest = [f for f in by_file if f not in dense]
    share = sum(len(by_file[f]) for f in rest) / max(1, len(dense))
    order, given = [], 0
    for i, f in enumerate(dense):
        order.append(f)
        while rest and given < share * (i + 1):
            given += len(by_file[rest[0]])
            order.append(rest.pop(0))
    items[:] = [item for f in order + rest for item in by_file[f]]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy variants excluded from the tier-1 run (-m 'not slow')")
