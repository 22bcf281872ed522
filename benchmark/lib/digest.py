"""The simulated statistics of a run, and their SHA-256.

A statistic is one named integer array (or scalar) of `SimResults`.  Each
is hashed over its name, shape and little-endian int64 values, so the same
numbers give the same hash whichever engine, backend or interpreter made
them; the comparison with a reference is exact, statistic by statistic.
"""

import dataclasses
import hashlib

import numpy as np

# SimResults fields that are instruments, not statistics of the target
_NOT_STATISTICS = ("telemetry", "profile", "hist")


def statistics(res) -> "dict[str, np.ndarray]":
    """Every simulated statistic of a `SimResults` (or of a
    `GoldenResult`: the fields it has), flat, by name."""
    out = {}
    for f in dataclasses.fields(res):
        v = getattr(res, f.name)
        if f.name in _NOT_STATISTICS or v is None:
            continue
        if isinstance(v, dict):
            for k, a in v.items():
                out[f"{f.name}.{k}"] = np.asarray(a)
        else:
            out[f.name] = np.asarray(v)
    if hasattr(res, "total_instructions"):
        out["total_instructions"] = np.asarray(res.total_instructions)
    return out


def sha_of(name: str, value) -> str:
    a = np.asarray(value)
    if a.dtype.kind not in "iub":
        raise TypeError(f"statistic {name} is {a.dtype}, not an integer")
    a = np.ascontiguousarray(a.astype("<i8"))
    h = hashlib.sha256()
    h.update(f"{name}|{a.shape}|".encode())
    h.update(a.tobytes())
    return h.hexdigest()


def hashes(stats: dict) -> "dict[str, str]":
    return {k: sha_of(k, stats[k]) for k in sorted(stats)}


def combined(hs: dict) -> str:
    """One digest over per-statistic hashes (those named in `hs`)."""
    h = hashlib.sha256()
    for k in sorted(hs):
        h.update(f"{k}:{hs[k]}\n".encode())
    return h.hexdigest()


def compare(res_hashes: dict, reference: dict) -> "list[str]":
    """Names of the reference's statistics that the run does not
    reproduce exactly (missing counts as differing)."""
    return [k for k, want in sorted(reference.items())
            if res_hashes.get(k) != want["sha256"]]
