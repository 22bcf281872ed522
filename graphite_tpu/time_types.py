"""Simulated-time types: picosecond-resolution Time and cycle Latency.

Reference semantics: `common/misc/time_types.h:7-119`.
 - Time is an integer picosecond count (`time_types.h:31-78`).
 - Latency is (cycles, frequency-in-GHz); conversion to picoseconds is
   ceil(1000 * cycles / frequency) (`time_types.h:81-86`).
 - Time.toCycles(frequency) = ceil(ps * frequency / 1000) (`time_types.h:104-109`).
 - Time.toNanosec = ceil(ps / 1000) (`time_types.h:111-114`).

Design differences for the TPU build:
 - Frequencies are carried as *integer megahertz* so every conversion is exact
   integer ceil-division — device code (int32/int64 tensors) and host code
   produce bit-identical results, which the determinism tests rely on.  The
   reference's `double`-based ceil matches integer ceil-div for every
   frequency expressible in MHz (all of `technology/dvfs_levels_*.cfg` is).
 - Both scalar-host and jnp-tensor forms are provided; the tensor forms are
   what the vectorized models use.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

from graphite_tpu.intmath import nn_ceil_div

# Conversion factors.
PS_PER_NS = 1000
PS_PER_CYCLE_NUMERATOR = 1_000_000  # ps/cycle = 1e6 / freq_mhz


def ghz_to_mhz(freq_ghz: float) -> int:
    """Represent a GHz float frequency exactly as integer MHz."""
    mhz = round(freq_ghz * 1000.0)
    if mhz <= 0:
        raise ValueError(f"non-positive frequency: {freq_ghz} GHz")
    return int(mhz)


def _ceil_div(a, b):
    """Ceil division for non-negative ints; works on ints and jnp arrays.

    Every caller's operands are non-negative by contract (cycle counts,
    picosecond durations, MHz frequencies), so the device form routes
    through `intmath.nn_ceil_div` — a single `lax.div` instead of the
    ~9-equation sign-fixup chain jnp's `//` lowers to, bit-identical on
    non-negative operands (PERF.md round 12)."""
    return nn_ceil_div(a, b)


def _ps_per_cycle(freq_mhz):
    """ps per cycle = 1e6 / freq_mhz as a (numerator, denominator) pair.

    A STATIC frequency - a Python or numpy integer, what every config-read
    `*_freq_mhz` is - is reduced by its gcd with 1e6 here, at trace time:
    ceil(c*p*g / (q*g)) = ceil(c*p / q) exactly, so a frequency that
    divides 1e6 (1,000 MHz: every shipped target's network, directory and
    DRAM clock) leaves a multiplication where the chip would emulate an
    int64 division, and any other divides by the smaller constant.  A
    TRACED frequency (a DVFS table riding the carry, a swept knob) cannot
    be reduced and keeps the full ratio: the choice hangs on what the
    argument is, not on an option."""
    if isinstance(freq_mhz, (int, np.integer)):
        g = math.gcd(PS_PER_CYCLE_NUMERATOR, int(freq_mhz))
        return PS_PER_CYCLE_NUMERATOR // g, int(freq_mhz) // g
    return PS_PER_CYCLE_NUMERATOR, freq_mhz


def _scale(x, num, den):
    """ceil(x * num / den) for non-negative x; a STATIC 1 (what
    `_ps_per_cycle` reduces to) neither multiplies nor divides."""
    def one(v):
        return isinstance(v, int) and v == 1

    if not one(num):
        x = x * num
    return x if one(den) else _ceil_div(x, den)


def cycles_to_ps(cycles, freq_mhz):
    """Latency::toPicosec (`time_types.h:81-86`): ceil(1e6*cycles/freq_mhz).

    Works elementwise on jnp int arrays (int64 recommended), numpy arrays
    and python ints.  Operands stay where they are: Python ints and numpy
    arrays are converted on the host (the result is an int / a numpy
    array), `jax.Array`s on the device.  A static `freq_mhz` is reduced
    first (`_ps_per_cycle`): bit-identical for every non-negative operand,
    with no division where it divides 1e6.
    """
    num, den = _ps_per_cycle(freq_mhz)
    return _scale(cycles, num, den)


def ps_to_cycles(ps, freq_mhz):
    """Time::toCycles (`time_types.h:104-109`): ceil(ps*freq_mhz/1e6).

    The inverse ratio of `cycles_to_ps`, reduced the same way for a
    static `freq_mhz` (at 1,000 MHz: ceil(ps / 1000)); host operands stay
    on the host."""
    den, num = _ps_per_cycle(freq_mhz)
    return _scale(ps, num, den)


def ps_to_ns(ps):
    """Time::toNanosec (`time_types.h:111-114`): ceil(ps/1000)."""
    return _ceil_div(ps, PS_PER_NS)


def ns_to_ps(ns):
    return ns * PS_PER_NS


@dataclasses.dataclass(frozen=True, order=True)
class Time:
    """Host-side scalar simulated time, integer picoseconds.

    Mirrors `common/misc/time_types.h:31-78`.  Device-side code uses raw
    int64 tensors of picoseconds; this wrapper is for host orchestration,
    config parsing, and summaries.
    """

    ps: int = 0

    def __add__(self, other: "Time | Latency") -> "Time":
        if isinstance(other, Latency):
            return Time(self.ps + other.to_ps())
        return Time(self.ps + other.ps)

    def __sub__(self, other: "Time") -> "Time":
        return Time(self.ps - other.ps)

    def to_cycles(self, freq_mhz: int) -> int:
        return ps_to_cycles(self.ps, freq_mhz)

    def to_ns(self) -> int:
        return ps_to_ns(self.ps)

    def to_sec(self) -> float:
        return self.ps / 1.0e12

    @staticmethod
    def from_ns(ns: int) -> "Time":
        return Time(ns * PS_PER_NS)

    @staticmethod
    def from_cycles(cycles: int, freq_mhz: int) -> "Time":
        return Time(cycles_to_ps(cycles, freq_mhz))


@dataclasses.dataclass(frozen=True)
class Latency:
    """Host-side (cycles, frequency) pair; `time_types.h:7-29`.

    Adding latencies requires matching frequencies, as in the reference
    (`time_types.h:88-102`).
    """

    cycles: int
    freq_mhz: int

    def __add__(self, other: "Latency") -> "Latency":
        if self.freq_mhz != other.freq_mhz:
            raise ValueError(
                "Attempting to add latencies from different frequencies"
            )
        return Latency(self.cycles + other.cycles, self.freq_mhz)

    def to_ps(self) -> int:
        return cycles_to_ps(self.cycles, self.freq_mhz)

    def to_time(self) -> Time:
        return Time(self.to_ps())


# --- Device-side helpers -------------------------------------------------

TIME_DTYPE = jnp.int64  # absolute simulated times
DELTA_DTYPE = jnp.int32  # per-quantum deltas (quantum ≤ ~2ms always fits)


def time_zeros(shape):
    return jnp.zeros(shape, dtype=TIME_DTYPE)
