"""JAX AOT executable codec for the persistent program store.

The store's payloads are real compiled executables, not lowerings:
`jax.experimental.serialize_executable` pickles a `jax.stages.Compiled`
(the XLA executable plus its calling convention) and loads it back
WITHOUT retracing or recompiling — the whole point of the store is that
a warm-started fleet pays deserialize seconds, never compile seconds.

The input/output pytree definitions ride inside the payload (they
pickle alongside the executable), so a payload is self-contained: the
loader needs only the bytes plus an import of `graphite_tpu` (which
registers the custom pytree nodes the trees reference).

Two caveats this module owns:

 - **Executables are environment-bound.**  A serialized executable is
   only valid on the jax/jaxlib version, backend platform, and device
   topology it was compiled for — `runtime_env()` is that identity
   tuple, and it is part of the store key AND re-verified at load, so a
   drifted environment reads as a clean miss (or a quarantined entry),
   never a crash deep inside the runtime.
 - **Payloads are pickle.**  Deserializing executes pickle, so a store
   directory must be as trusted as the code itself (the same trust a
   shared XLA compilation cache already requires).  The integrity layer
   (sha256 checksums, store/store.py) protects against corruption, not
   against a malicious writer.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import pickle

# bumped whenever the payload tuple layout changes — an old payload
# under a new reader is an integrity error, not a crash
PAYLOAD_FORMAT = "graphite-aot-payload-v2"


def runtime_env() -> "tuple[str, str, str, str, int]":
    """The environment identity a serialized executable is bound to:
    (jax version, jaxlib version, backend platform, device KIND,
    device count).  The kind axis keys a heterogeneous fleet apart:
    two accelerator generations report the same backend string
    ("tpu", "gpu") but compile incompatible XLA targets — without it
    they would share one entry and quarantine each other's healthy
    executables in a recompile ping-pong."""
    import jax
    import jaxlib

    devs = jax.devices()
    kind = devs[0].device_kind if devs else "?"
    return (jax.__version__, jaxlib.__version__, jax.default_backend(),
            str(kind), jax.device_count())


@contextlib.contextmanager
def _fresh_codegen():
    """Bypass the JAX persistent compilation cache for one compile.

    A `.compile()` served from the persistent cache returns an
    executable DESERIALIZED from the cache payload — and re-serializing
    a deserialized XLA:CPU executable silently drops the object code
    its kernels live in, so the store would publish a payload that dies
    at load with "Symbols not found".  Only a cold compile (real
    codegen) captures every kernel symbol.  jax initialises its cache
    object once per process, so un-setting the directory does not stop
    an initialised cache being consulted; `jax_enable_compilation_cache
    = False` followed by `reset_cache()` does (the reset also clears
    jax's memoised "is the cache used" answer).  Both are restored
    exactly on exit, and the next compile re-opens the same directory.
    The program store subsumes the role the XLA cache played for these
    programs anyway — one deliberate cold compile per FLEET beats a
    warm compile that cannot be shared."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        compilation_cache.reset_cache()


# monotonically unique per-process AOT compile names (see
# aot_compile_runner: identical HLO must not dedup against resident
# executables, or the serialized artifact loses their object code)
_aot_counter = itertools.count()


def aot_compile_runner(runner, max_quanta: int):
    """AOT-compile a `SweepRunner`'s batched campaign function against
    its REAL device inputs (aval-exact, so the compiled executable
    accepts exactly the arrays `run()` passes) and inject it as the
    runner's executable.  Returns the `jax.stages.Compiled` — callable
    and serializable, bit-identical to the lazy `jax.jit` path (same
    lowering, same XLA optimization pipeline).

    Two measures keep the executable FULLY serializable (both measured
    necessary, see `_fresh_codegen` and the store README section):
    the persistent-cache bypass, and a process-unique function name.
    The name defeats in-memory dedup against identical already-resident
    executables — a deduped compile returns an executable whose
    serialization omits the object code the resident copy already
    carries, poisoning any process that later compiles a program it
    previously loaded (quarantine-refill, multi-class services).  The
    name only enters the HLO module label: the canonical jaxpr
    fingerprint (`analysis/identity`) and the numerics are invariant
    under it (test-pinned)."""
    import jax

    fn = runner._runner_fn(max_quanta)

    def campaign(states, dtr, knobs):
        return fn(states, dtr, knobs)

    campaign.__name__ = f"campaign_aot_{os.getpid()}_{next(_aot_counter)}"
    states0, dtr = runner._batched_inputs()
    with _fresh_codegen():
        compiled = jax.jit(campaign).lower(
            states0, dtr, runner.knobs).compile()
    runner._runner = compiled
    runner._runner_max_quanta = max_quanta
    return compiled


def serialize_compiled(compiled) -> bytes:
    """One self-contained payload blob for a `jax.stages.Compiled`:
    (format tag, executable bytes, in_tree, out_tree, device ids),
    pickled.  The ids are the devices the executable was compiled over,
    in assignment order: the loader must name them, or jax loads the
    executable over EVERY device of the backend and a single-device
    program then refuses its arguments on a multi-device host."""
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    device_ids = tuple(
        d.id for d in compiled.runtime_executable().local_devices())
    return pickle.dumps(
        (PAYLOAD_FORMAT, payload, in_tree, out_tree, device_ids),
        protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_compiled(blob: bytes):
    """Load a payload blob back into a callable executable.  Raises
    `ValueError` on a foreign or malformed blob — the store maps any
    failure here to a quarantining `StoreIntegrityError`."""
    import jax
    from jax.experimental import serialize_executable as se

    try:
        obj = pickle.loads(blob)
    except Exception as e:
        raise ValueError(f"payload does not unpickle: "
                         f"{type(e).__name__}: {e}") from e
    if (not isinstance(obj, tuple) or len(obj) != 5
            or obj[0] != PAYLOAD_FORMAT):
        raise ValueError("payload is not a "
                         f"{PAYLOAD_FORMAT!r} blob")
    _, payload, in_tree, out_tree, device_ids = obj
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])
