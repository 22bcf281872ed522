"""The fingerprint-keyed compiled-program cache.

A cache entry is one jitted batched-campaign callable (the
`SweepRunner` runner function) plus the `analysis/registry`
`ProgramRecord` that proves WHAT it is: the canonical jaxpr fingerprint
(`analysis/identity.fingerprint`) of the lowering it was compiled from.
The service resolves every insert and hit through its registry, so

 - at INSERT time, the freshly lowered program's fingerprint must match
   the registered identity for that key (first insert registers it) —
   a mismatch raises `ProgramCacheError` LOUDLY instead of silently
   caching a program that is not what the key claims (e.g. a re-lowered
   class that drifted after an eviction);
 - at HIT time, the stored record must still resolve to the registered
   fingerprint, and (with `verify_hits`) the service re-lowers the new
   batch and re-proves fingerprint equality — a retrace, never a
   recompile, so the round-7 compile-count probe still reads 1.

Since round 17 the in-memory cache can sit over a persistent
fingerprint-keyed store of serialized executables (`store/`): a miss
consults the store before compiling, a fresh compile fills it, and
`CacheEntry.source` records which path materialized the entry — the
service (`serve/service.py _resolve_program`) owns that layering, this
module stays pure host-side bookkeeping.

Eviction is byte-accounted LRU: each entry carries the residency bill
of the campaign layout it serves (the same
`analysis/cost.residency_breakdown` total the admission controller
budgets), and inserts evict least-recently-used entries until the cache
total fits `max_bytes` (0 = unbounded).  The newest entry is never
evicted — a cache that cannot hold one program would force a compile
per batch, which is strictly worse than admitting the overage.
"""

from __future__ import annotations

import collections
import dataclasses


class ProgramCacheError(RuntimeError):
    """A cache entry failed identity or shape verification."""


@dataclasses.dataclass
class CacheEntry:
    """One compiled campaign program + its provable identity."""

    name: str                 # registry key (human-readable class name)
    record: object            # analysis.registry.ProgramRecord
    jitted: object            # the jitted runner callable
    max_quanta: int
    nbytes: int               # residency bill of the layout it serves
    shape_sig: tuple          # (B, n_tiles, pad_length)
    # host seconds the miss paid to lower + fingerprint + set up the
    # jit (round 14 observability — batch spans report it on hits too,
    # so "what did this program cost to build" survives the miss)
    compile_s: float = 0.0
    # round 17: how this entry materialized — "compile" (lowered and
    # compiled in this process) or "store" (deserialized from the
    # persistent AOT program store) — and the host seconds the store
    # hit paid to deserialize the payload (0.0 for in-process compiles;
    # for store entries compile_s reports what the ORIGINAL fleet miss
    # paid, read from the entry manifest)
    source: str = "compile"
    deserialize_s: float = 0.0
    # what `ResidentProgram` reads: the program's inputs as shapes, the
    # last batch's loop trip count and launches, and the tracer that
    # follows the program's dispatches
    inputs: "tuple | None" = None
    last_n_iterations: int = 0
    last_run_dispatches: int = 0
    tracer: object = None


class ResidentProgram:
    """A handle to the resident program of one class: the cached
    executable as the service keeps dispatching it, with the hooks a
    `Simulator` has for its own program - `attach_tracer`,
    `compiled_text`, `last_n_iterations`, `last_run_dispatches` - so
    that whatever attributes a `Simulator`'s program by scope can
    attribute a served one (`CampaignService.resident_program`)."""

    def __init__(self, entry: CacheEntry):
        self.entry = entry

    @property
    def name(self) -> str:
        return self.entry.name

    def attach_tracer(self, tracer) -> None:
        """Every later batch of this class records its `SweepRunner.run`
        spans (`run` > `dispatch` > `wait` > `fetch` > `results`) in
        `tracer` under a `run-<n>` trace; None detaches (the spans then
        go to the service's own tracer, under the batch's trace)."""
        self.entry.tracer = tracer

    def compiled_text(self) -> str:
        """Optimized HLO text of the executable the class's batches
        dispatch, each instruction with its `op_name` path; no compile."""
        from graphite_tpu.sweep.runner import executable_text

        if self.entry.inputs is None:
            raise ValueError(f"program {self.name!r} has served no batch")
        return executable_text(self.entry.jitted, self.entry.inputs)

    @property
    def last_n_iterations(self) -> int:
        """Loop trip count of the last batch served (max over its sims)."""
        return self.entry.last_n_iterations

    @property
    def last_run_dispatches(self) -> int:
        return self.entry.last_run_dispatches


class ProgramCache:
    """Byte-accounted LRU over compiled campaign programs."""

    def __init__(self, max_bytes: int = 0):
        self.max_bytes = int(max_bytes)
        self._entries: "collections.OrderedDict[tuple, CacheEntry]" = \
            collections.OrderedDict()
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    @property
    def total_bytes(self) -> int:
        return sum(e.nbytes for e in self._entries.values())

    def keys(self):
        return list(self._entries)

    def get(self, key, shape_sig: "tuple | None" = None
            ) -> "CacheEntry | None":
        """LRU-touching lookup.  `shape_sig` guards the one silent
        failure mode jit would otherwise hide: calling a cached
        callable with different input shapes would quietly COMPILE a
        second executable instead of erroring — a shape mismatch here
        means the class key failed to capture a shape-bearing input and
        must be fixed, not papered over."""
        entry = self._entries.get(key)
        if entry is None:
            return None
        if shape_sig is not None and tuple(shape_sig) != entry.shape_sig:
            raise ProgramCacheError(
                f"cache entry {entry.name!r} serves shape "
                f"{entry.shape_sig} but the batch asks for "
                f"{tuple(shape_sig)} — the class key missed a "
                "shape-bearing input (calling through would silently "
                "recompile)")
        self._entries.move_to_end(key)
        return entry

    def put(self, key, entry: CacheEntry, *,
            expect_fingerprint: str) -> CacheEntry:
        """Insert with identity verification: `expect_fingerprint` is
        the registry-resolved identity for this key, and the entry's
        record must match it — a registry-mismatched fingerprint at
        insert time errors loudly instead of silently serving a stale
        (or wrong) program under the key's name."""
        if entry.record.fingerprint != expect_fingerprint:
            raise ProgramCacheError(
                f"refusing to cache {entry.name!r}: lowered fingerprint "
                f"{entry.record.fingerprint[:24]}... does not match the "
                f"registered identity {expect_fingerprint[:24]}... — "
                "the program drifted from what this key previously "
                "compiled; a silent insert would serve a different "
                "artifact under the same name")
        self._entries[key] = entry
        self._entries.move_to_end(key)
        while (self.max_bytes and len(self._entries) > 1
               and self.total_bytes > self.max_bytes):
            self._entries.popitem(last=False)
            self.evictions += 1
        return entry
