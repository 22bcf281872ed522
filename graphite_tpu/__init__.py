"""graphite_tpu — a TPU-native tile-array multicore simulator.

A brand-new JAX/XLA/Pallas framework with the capabilities of MIT's Graphite
distributed multicore simulator (reference: nmtrmail/Graphite).  Instead of
Graphite's two-host-threads-per-tile + TCP-socket transport design
(`common/system/sim_thread.cc`, `common/transport/socktransport.cc`), all tile
state lives in a struct-of-arrays tensor sharded over the TPU's ICI mesh and
every tile advances one lax-barrier quantum per compiled XLA step.

Layer map (mirrors SURVEY.md §1, reference layers L0–L7):

    frontend/   the user-API surface (carbon_api live recording — the
                routine-replacement analog); trace/ holds the producers
    trace/      record schema, synthetic generators, benchmark skeletons
    config/     carbon_sim.cfg-compatible config + target-topology math
    models/     core timing (simple/iocoom), NoC models, DVFS, queue models
    memory/     cache arrays + coherence protocol engines (MSI/MOSI/shL2)
    engine/     the quantum-step state machine + Simulator orchestration
    golden/     sequential differential oracles (core + memory hierarchy)
    parallel/   device-mesh sharding: shard_map packed exchange (default
                multi-chip program) + legacy GSPMD specs, over ICI
    power/      McPAT/DSENT-equivalent energy models fed by event counters
    system/     host-side MCP analogs: threads, syscalls, stats, checkpoint
    tools/      drivers (graduated runner, sweep, serve, output parsing)

Simulated time is exact integer picoseconds throughout
(reference: `common/misc/time_types.h:31-78`), so the package enables
jax_enable_x64 at import.  Hot per-quantum deltas still use int32 internally.
"""

import time as _time

_T_IMPORT = _time.perf_counter()    # the set-up span `import` starts here

import os  # noqa: E402

import jax  # noqa: E402

# Picosecond-resolution simulated time needs 64-bit integers (a 1 GHz tile
# overflows int32 picoseconds after ~2ms of simulated time).  TPUs emulate
# int64 in pairs of int32 ops; the hot kernels keep deltas in int32.
jax.config.update("jax_enable_x64", True)

# The compiled quantum loop is a large program (core + protocol + NoC +
# sync FSMs fused into one while_loop); cold compiles run minutes at
# large tile counts.  Cache compilations persistently so repeat runs of
# the same topology start in seconds.  JAX_COMPILATION_CACHE_DIR, when
# set, places the cache and nothing is set here; otherwise it lives at
# one fixed path inside the checkout (the path is part of the cache key,
# so it must never move between runs).
if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
        and jax.config.jax_compilation_cache_dir is None):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)

__version__ = "0.1.0"

from graphite_tpu.time_types import Time, Latency  # noqa: E402,F401
from graphite_tpu.config import ConfigFile, SimConfig  # noqa: E402,F401
from graphite_tpu.obs import trace as _trace  # noqa: E402

# first line to last, jax with it (obs/trace.py: SETUP_SPANS); importing
# `obs.trace` also installs the program ledger's one listener
_trace.SETUP.record(_trace.SETUP_TRACE_ID, "import", _T_IMPORT,
                    _time.perf_counter())
