"""PR 50, step 0. Run: `chiprun --chips 1 -- python _hand/fanout50.py` (numbers: PERF.md section 6, PR 50).

What does one `memory/engine.py: mem_net_fanout` + the transposed select of
its `[T, T]` arrival matrix into a carried `int64[T, T]` forward mailbox
(what `_home_starts` does with it) cost on the chip, alone, at T = 1,024,
for the three memory-network kinds, in two forms of the same integers?

    as_was  the time conversion divides by the full ratio 1e6 / f: the
            program before PR 50 (hop counter: one `div int64[1024,1024]`;
            ATAC: two and a `cumsum`; hop_by_hop: one)
    ratio   as shipped: `time_types._ps_per_cycle` reduces 1e6 / f by its
            gcd at trace time (1,000 MHz: a multiplication)

`as_was` is made by un-patching the ONE name PR 50 added
(`time_types._ps_per_cycle`), at trace time only: the tree keeps one form.
Before anything is timed the two forms are shown to be different programs
(the `div`s with a `[T, T]` result in each one's jaxpr: some, none) that
give the same words.  (The first session of PR 50 also timed a third form,
the zero-load matrix folded on the host into an 8 MB constant; it bought
nothing at 1,000 MHz - PERF.md section 6 - and was not shipped.)

    JAX_PLATFORMS=cpu python _hand/fanout50.py --rehearse   # tiny trip counts, T = 64, the forms against each other
    JAX_PLATFORMS=cpu python _hand/fanout50.py --describe   # T = 1,024 through the TPU compiler, no chip: fusions a trip, seconds of compile
    chiprun --chips 1 -- python _hand/fanout50.py           # the table, on the chip

Times, in a `fori_loop` of n trips whose body is ONE `lax.cond` (as a home
phase is; PERF.md section 7, PR 46 (4): a carried table outside a cond may
be kept in fast memory) over the carried NoC state and the carried
`fwd_time int64[T, T]`: the send matrix moves every trip (0.2% / 50% /
100% of the pairs, the last a broadcast sweep), `t0_ps` advances 150 ns a
trip, `enabled` is a traced True.  The price of a trip is the slope
between two trip counts (dispatch and launch cancel).  Every form's final
mailbox and queue table are compared word for word before anything is
timed.
"""
import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"),
                os.path.join(ROOT, "tests")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import graphite_tpu  # noqa: E402,F401  (x64 + compile cache placement)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from lib import target  # noqa: E402

from graphite_tpu import time_types as tt  # noqa: E402
from graphite_tpu.memory import engine  # noqa: E402
from graphite_tpu.memory.params import MemParams  # noqa: E402
from targets import fresh_mem_noc  # noqa: E402

I64 = jnp.int64
CELL = target.load_config("atac-ackwise-1024-memstress")
# kind -> the config_text keys laid over the ATAC cell's (its mesh, caches
# and directory are `coh-1024-memstress`'s)
KINDS = {"emesh_hop_counter": {"network": "emesh_hop_counter"},
         "atac": {},
         "emesh_hop_by_hop": {"network": "emesh_hop_by_hop"}}
FORMS = ("as_was", "ratio")


def mem_params(kind, tiles):
    text = {**CELL["config_text"], **KINDS[kind], "tiles": tiles}
    if tiles < 1024:
        text["atac_cluster_size"] = 4
    return MemParams.from_config(target.build_sim_config(
        {"config_text": text}))


@contextlib.contextmanager
def traced_as(form):
    """Trace under `form`: `as_was` takes out what PR 50 added."""
    saved = tt._ps_per_cycle
    if form == "as_was":
        tt._ps_per_cycle = lambda f: (tt.PS_PER_CYCLE_NUMERATOR, f)
    try:
        yield
    finally:
        tt._ps_per_cycle = saved


def matrix_divisions(mp, form, args):
    """`div` / `rem` with a [T, T] result in the form's jaxpr: what makes
    the forms different programs (a rename of the patched name would time
    one program twice, silently)."""
    from graphite_tpu.analysis.walk import iter_eqns

    T = mp.n_tiles
    with traced_as(form):
        jaxpr = jax.make_jaxpr(looped(mp))(*args, jnp.int32(0))
    return sum(1 for e in iter_eqns(jaxpr)
               if e.primitive.name in ("div", "rem")
               and e.outvars[0].aval.shape == (T, T))


def looped(mp):
    """n fan-outs + selects in one program, each inside a cond."""
    T = mp.n_tiles

    def run(noc, fwd, u, enabled, n):
        def body(i, carry):
            def phase(c):
                noc, fwd = c
                thr = jnp.asarray([0.002, 0.5, 2.0], jnp.float32)[i % 3]
                send = u < thr
                t0 = (1_000_000 + 1_003 * (jnp.arange(T, dtype=I64) % 7)
                      + 150_000 * i.astype(I64))
                noc, arrive = engine.mem_net_fanout(
                    mp, noc, send, mp.req_bits, t0, enabled)
                return noc, jnp.where(send.T, arrive.T, fwd)

            return jax.lax.cond(enabled, phase, lambda c: c, carry)

        return jax.lax.fori_loop(0, n, body, (noc, fwd))

    return jax.jit(run)


def arguments(mp, seed=50):
    T = mp.n_tiles
    u = np.random.default_rng(seed).random((T, T), np.float32)
    return (fresh_mem_noc(mp), jnp.zeros((T, T), I64), jnp.asarray(u),
            jnp.asarray(True))


def price(fn, args, trips):
    """Seconds a trip: the slope between the two trip counts, the best of
    three walls each."""
    walls = []
    for n in trips:
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args, n))
            best = min(best, time.perf_counter() - t0)
        walls.append(best)
    return (walls[1] - walls[0]) / (trips[1] - trips[0]), walls


def describe():
    """T = 1,024 through the TPU compiler for a described v5e: the
    fusions a trip of each form holds, its [T, T] divisions as traced, and
    the seconds the compiler took."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=one)

    for kind in KINDS:
        mp = mem_params(kind, 1024)
        shapes = jax.tree.map(sds, arguments(mp) + (jnp.int32(0),))
        for form in FORMS:
            t0 = time.perf_counter()
            with traced_as(form):
                lowered = looped(mp).lower(*shapes)
            text = lowered.compile().as_text()
            lines = text.splitlines()
            kinds = {k: sum(1 for ln in lines if f" {k}(" in ln and "=" in ln)
                     for k in ("fusion", "while", "conditional")}
            kinds["div[T,T] traced"] = matrix_divisions(
                mp, form, arguments(mp))
            print(f"{kind:18s} {form:7s}: compiled in "
                  f"{time.perf_counter() - t0:.1f} s, {len(text):,} bytes of "
                  f"HLO text, {kinds}", flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe", action="store_true")
    a = ap.parse_args()
    if a.describe:
        return describe()
    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print(f"needs a TPU (found {dev.platform}); --rehearse on the CPU")
        return 1
    tiles, trips = (64, (3, 9)) if a.rehearse else (1024, (60, 360))
    rows = []
    for kind in KINDS:
        mp = mem_params(kind, tiles)
        args = arguments(mp)
        divs = {form: matrix_divisions(mp, form, args) for form in FORMS}
        assert divs["as_was"] >= 1 and divs["ratio"] == 0, (kind, divs)
        fns, outs = {}, {}
        for form in FORMS:
            with traced_as(form):
                fns[form] = looped(mp).lower(*args, jnp.int32(0)).compile()
            outs[form] = jax.block_until_ready(
                fns[form](*args, jnp.int32(trips[0])))
        want = jax.tree.leaves(outs["as_was"])
        same = all(
            bool((x == y).all())
            for form in FORMS
            for x, y in zip(jax.tree.leaves(outs[form]), want))
        row = {"kind": kind, "tiles": tiles, "identical": same,
               "matrix_divisions": divs,
               "net_freq_mhz": int(mp.net_freq_mhz if mp.net_atac is None
                                   else mp.net_atac.freq_mhz),
               "fwd_time_sum": int(np.asarray(want[-1]).sum())}
        for form, fn in fns.items():
            s, walls = price(
                lambda *xs, fn=fn: fn(*xs[:-1], jnp.int32(xs[-1])),
                args, trips)
            row[f"{form}_us_a_call"] = 1e6 * s
            row[f"{form}_walls_s"] = walls
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "fanout50.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "device_kind": dev.device_kind},
                   "trips": trips, "rows": rows}, f, indent=1)
    return 0 if all(r["identical"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
