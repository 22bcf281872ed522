"""Time/Latency semantics vs `common/misc/time_types.h:81-119`."""

import math

import jax.numpy as jnp
import pytest

from graphite_tpu.time_types import (
    Latency,
    Time,
    cycles_to_ps,
    ghz_to_mhz,
    ps_to_cycles,
    ps_to_ns,
)


def ref_latency_to_ps(cycles: int, freq_ghz: float) -> int:
    """The reference's double-based ceil (`time_types.h:81-86`)."""
    return int(math.ceil((1000.0 * cycles) / freq_ghz))


def ref_time_to_cycles(ps: int, freq_ghz: float) -> int:
    """`time_types.h:104-109`."""
    return int(math.ceil((float(ps) * freq_ghz) / 1.0e3))


@pytest.mark.parametrize("freq_ghz", [0.5, 1.0, 1.5, 2.0, 2.5, 3.3])
@pytest.mark.parametrize("cycles", [0, 1, 2, 3, 7, 100, 999, 12345])
def test_cycles_to_ps_matches_reference(freq_ghz, cycles):
    got = cycles_to_ps(cycles, ghz_to_mhz(freq_ghz))
    want = ref_latency_to_ps(cycles, freq_ghz)
    assert got == want


@pytest.mark.parametrize("freq_ghz", [0.5, 1.0, 2.0, 2.5])
@pytest.mark.parametrize("ps", [0, 1, 499, 500, 501, 1000, 123456, 10**9])
def test_ps_to_cycles_matches_reference(freq_ghz, ps):
    got = ps_to_cycles(ps, ghz_to_mhz(freq_ghz))
    want = ref_time_to_cycles(ps, freq_ghz)
    assert got == want


def test_ps_to_ns_is_ceil():
    # `time_types.h:111-114`
    assert ps_to_ns(0) == 0
    assert ps_to_ns(1) == 1
    assert ps_to_ns(1000) == 1
    assert ps_to_ns(1001) == 2


def test_vectorized_matches_scalar():
    cycles = jnp.array([0, 1, 3, 999, 12345], dtype=jnp.int64)
    out = cycles_to_ps(cycles, ghz_to_mhz(2.0))
    assert out.dtype == jnp.int64
    for c, o in zip([0, 1, 3, 999, 12345], out.tolist()):
        assert o == ref_latency_to_ps(c, 2.0)


def test_time_latency_host_types():
    t = Time.from_ns(5)
    assert t.ps == 5000
    t2 = t + Latency(cycles=8, freq_mhz=1000)
    assert t2.ps == 5000 + 8000
    assert (t2 - t).ps == 8000
    assert t2.to_ns() == 13
    assert Time(1500).to_ns() == 2  # ceil


def test_latency_add_requires_same_frequency():
    with pytest.raises(ValueError):
        Latency(1, 1000) + Latency(1, 2000)
    assert (Latency(2, 1000) + Latency(3, 1000)).cycles == 5


def test_int64_no_overflow():
    # 10 seconds of simulated time in ps exceeds int32
    t = jnp.asarray(10**13, dtype=jnp.int64)
    assert int(ps_to_ns(t)) == 10**10


# --- PR 50: a static frequency's 10^6 / f is reduced at trace time --------

FREQS_MHZ = [500, 1000, 2000, 870, 630, 1500, 3300]   # four divide 10^6
CYCLES = [0, 1, 7, 999, 12345, 2**31 + 3, 2**40]
PS = [0, 1, 499, 1001, 10**9 + 7, 2**44 + 5, 2**50]


def ref_c2p(c: int, f: int) -> int:
    return -(-c * 10**6 // f)


def ref_p2c(ps: int, f: int) -> int:
    return -(-ps * f // 10**6)


@pytest.mark.parametrize("f", FREQS_MHZ)
@pytest.mark.parametrize("fn,ref,xs", [(cycles_to_ps, ref_c2p, CYCLES),
                                       (ps_to_cycles, ref_p2c, PS)],
                         ids=["cycles_to_ps", "ps_to_cycles"])
class TestReducedRatio:
    """The reduced forms equal the Python-integer ceil of the full ratio
    on every kind of operand, and each operand stays where it was."""

    def test_python_ints(self, fn, ref, xs, f):
        for x in xs:
            got = fn(x, f)
            assert type(got) is int and got == ref(x, f), (x, f)

    def test_numpy_stays_on_the_host(self, fn, ref, xs, f):
        import jax
        import numpy as np

        for freq in (f, np.int32(f)):
            got = fn(np.asarray(xs, np.int64), freq)
            assert isinstance(got, np.ndarray)
            assert not isinstance(got, jax.Array)
            assert got.dtype == np.int64
            assert got.tolist() == [ref(x, f) for x in xs]

    def test_device_int64(self, fn, ref, xs, f):
        got = fn(jnp.asarray(xs, jnp.int64), f)
        assert got.dtype == jnp.int64
        assert got.tolist() == [ref(x, f) for x in xs]

    def test_traced_frequency_takes_the_division_and_agrees(
            self, fn, ref, xs, f):
        """A frequency the program carries (a DVFS table, a swept knob)
        cannot be reduced: one `div` as before, the static one's values."""
        import jax

        x = jnp.asarray(xs, jnp.int64)
        fs = jnp.full(len(xs), f, jnp.int64)
        names = [e.primitive.name for e in jax.make_jaxpr(fn)(x, fs).eqns]
        assert names.count("div") == 1
        assert jax.jit(fn)(x, fs).tolist() == fn(x, f).tolist()
        static = [e.primitive.name
                  for e in jax.make_jaxpr(lambda v: fn(v, f))(x).eqns]
        one = 10**6 % f == 0 if fn is cycles_to_ps else f % 10**6 == 0
        assert static.count("div") == (0 if one else 1)
