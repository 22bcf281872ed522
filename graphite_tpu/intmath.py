"""Non-negative integer division/modulo for device code.

`jnp`'s `//` and `%` implement Python floor semantics on signed ints,
which XLA receives as a ~9-equation sign-fixup chain (div/rem + two
signs + compares + select) per call site.  The memory engines compute
set indices, home mappings, bit positions, and ceil-division time
conversions hundreds of times per subquantum iteration, always on
values that are non-negative by construction (line numbers, tile ids,
cycle counts, picosecond durations) — where truncating and flooring
division agree exactly.  These helpers emit the single `lax.div` /
`lax.rem` equation instead; results are bit-identical to the floor
forms for non-negative operands (the golden interpreters pin this on
randomized traces).

CONTRACT: both operands must be provably >= 0 (divisor > 0).  Sites
where a value can be negative — e.g. victim lines read off an invalid
cache way (tag -1) — must keep the floor operators; see the round-12
notes in PERF.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _traced(*xs) -> bool:
    return any(isinstance(x, jax.Array) for x in xs)


def _pair(a, b):
    a = jnp.asarray(a)
    b = jnp.asarray(b, a.dtype) if not hasattr(b, "dtype") \
        else b.astype(a.dtype) if b.dtype != a.dtype else b
    shape = jnp.broadcast_shapes(jnp.shape(a), jnp.shape(b))
    return jnp.broadcast_to(a, shape), jnp.broadcast_to(b, shape)


def nn_mod(a, b):
    """`a % b` for non-negative `a`, positive `b` — one lax.rem.

    Python ints and numpy arrays stay host-side (truncating and floor
    modulo agree on non-negative operands), so constant operands fold to
    constants instead of equations."""
    if not _traced(a, b):
        return a % b
    a, b = _pair(a, b)
    return lax.rem(a, b)


def nn_div(a, b):
    """`a // b` for non-negative `a`, positive `b` — one lax.div."""
    if not _traced(a, b):
        return a // b
    a, b = _pair(a, b)
    return lax.div(a, b)


def nn_divmod(a, b):
    """(a // b, a % b) for non-negative operands."""
    return nn_div(a, b), nn_mod(a, b)


def nn_ceil_div(a, b):
    """ceil(a / b) for non-negative `a`, positive `b`."""
    x = a + b - 1
    if isinstance(x, jax.Array):
        return nn_div(x, b)
    return x // b
