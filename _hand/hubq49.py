"""PR 49, step 0. Run: `chiprun --chips 1 -- python _hand/hubq49.py` (numbers: PERF.md section 6, PR 49).

What does one `scatter_queue_delay` cost on the chip, alone, lowered dense
(as it is since PR 49) and by gather / scatter (as it was; the form
`tests/test_queue_models.py` keeps as the reference), at the shapes the
engines lower and between them?

    JAX_PLATFORMS=cpu python _hand/hubq49.py --rehearse   # tiny trip counts, both forms against each other
    JAX_PLATFORMS=cpu python _hand/hubq49.py --describe   # the cell's shape through the TPU compiler, no chip: the loop body's fusions
    chiprun --chips 1 -- python _hand/hubq49.py           # the table, on the chip

Times, in a `fori_loop` of n trips over a carried `[N, 10]` int64 queue
table, 1,024 lanes whose queues, times and mask move every trip
(`history_tree`, service 1 / 9 / 1,008, every third trip a thin 3% mask as
the cell's iterations are: a masked lane still scatters, onto the scratch
row): the SCATTER form (one gather, the M/G/1 wait a lane, four scatters
with conflicting indices) and the DENSE form (one-hot selections over the
queue axis, the wait a queue, reductions over the lane axis), at N = 129
(the ATAC's hubs), 1,025, 2,049 and 6,145 (the hop-by-hop fan-out's
ports).  The price of a trip is the slope between two trip counts
(dispatch and launch cancel).  Both forms' tables and summed delays after
the trips are compared word for word before anything is timed.

On the v5e (PR 49, us a call, scatter / dense): 129 queues 850.7 / 7.3,
1,025 1,014.1 / 24.9, 2,049 1,099.4 / 44.1, 6,145 1,191.4 / 209.4.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import graphite_tpu  # noqa: E402,F401  (x64 + compile cache placement)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from test_queue_models import _gather_scatter_reference  # noqa: E402

from graphite_tpu.models import queue_models as qm  # noqa: E402

I64 = jnp.int64
L = 1024
PARAMS = qm.QueueParams(kind="history_tree", max_list_size=100,
                        min_processing_time=1)

FORMS = {"scatter": _gather_scatter_reference,
         "dense": qm.scatter_queue_delay}


def inputs(N, seed=49):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, N - 1, L), jnp.int32),
            jnp.asarray(rng.integers(0, 400, L), I64),
            jnp.asarray(rng.choice([1, 9, 1008], L, p=[.6, .37, .03]), I64),
            jnp.asarray(rng.random(L), jnp.float32))


def looped(form, N):
    """n calls in one program; trip i moves every lane's queue by 7 i, the
    clock by 150 i cycles, and masks 30% / 97% / 30% of the lanes."""

    def run(q, qid0, dt, proc, u, n):
        def body(i, carry):
            q, acc = carry
            i32 = i.astype(jnp.int32)
            mask = u < jnp.where(i32 % 3 == 1, 0.03, 0.7)
            qid = jnp.where(mask, (qid0 + 7 * i32) % (N - 1), N - 1)
            t = jnp.maximum(150 * i.astype(I64) - dt, 0)
            q, delay = form(PARAMS, q, qid, t, proc, mask)
            return q, acc + delay.sum()

        return jax.lax.fori_loop(0, n, body, (q, jnp.zeros((), I64)))

    return jax.jit(run)


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
    """The cell's shape through the TPU compiler for a described v5e: how
    many fusions, gathers and scatters one trip of each form holds."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    table = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                         qm.make_queues(129, PARAMS))
    for name, form in FORMS.items():
        t0 = time.perf_counter()
        text = looped(form, 129).lower(
            table, sds((L,), jnp.int32), sds((L,), I64),
            sds((L,), I64), sds((L,), jnp.float32),
            sds((), jnp.int32)).compile().as_text()
        kinds = {k: sum(1 for ln in text.splitlines()
                        if f" {k}(" in ln and "=" in ln)
                 for k in ("fusion", "gather", "scatter", "while")}
        print(f"{name:8s} N 129: compiled in {time.perf_counter() - t0:.1f} "
              f"s, {len(text):,} bytes of HLO text, {kinds}")
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
    trips = (3, 9) if a.rehearse else (200, 1200)
    rows = []
    for N in ((129, 1025) if a.rehearse else (129, 1025, 2049, 6145)):
        args = (qm.make_queues(N, PARAMS),) + inputs(N)
        fns = {name: looped(form, N) for name, form in FORMS.items()}
        outs = {name: jax.block_until_ready(fn(*args, trips[0]))
                for name, fn in fns.items()}
        same = (bool((outs["dense"][0].data == outs["scatter"][0].data).all())
                and int(outs["dense"][1]) == int(outs["scatter"][1]))
        data = np.asarray(outs["dense"][0].data)
        row = {"queues": N, "lanes": L, "plane": N * L, "identical": same,
               "mg1_reads": int(data[:, qm.COL_ANA].sum()),
               "requests": int(data[:, qm.COL_REQS].sum())}
        for name, fn in fns.items():
            s, walls = price(fn, args, trips)
            row[f"{name}_us_a_call"] = 1e6 * s
            row[f"{name}_walls_s"] = walls
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "hubq49.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "device_kind": dev.device_kind},
                   "trips": trips, "rows": rows}, f, indent=1)
    return 0 if all(r["identical"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
