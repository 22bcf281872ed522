"""PR 45, step 0. Run: `chiprun --chips 1 -- python _hand/entry45.py` (numbers: PERF.md section 6, PR 45).

What does ONE landing of the home phases' entry-word plan cost, alone?

    JAX_PLATFORMS=cpu python _hand/entry45.py --counts coh-1024-memstress   # live words of every landing of a reading
    JAX_PLATFORMS=cpu python _hand/entry45.py --rehearse   # tiny, kernel interpreted
    JAX_PLATFORMS=cpu python _hand/entry45.py --describe   # real sizes through the TPU compiler, no chip: the loop body's big operations
    chiprun --chips 1 -- python _hand/entry45.py           # the table, on the chip

Times, on a donated store in a `fori_loop` of n trips whose sets move every
trip, what `engine._dir_apply_merged` does to the entry store an open
iteration - gather three set rows a lane, land a `[3, T]` plan of 8-byte
deltas - in three forms: the XLA scatter-add on `int64[T, DS, DW]` (the
parent of PR 45), `row_landing.land_entry` on the `u32[T, 2 * DW, DS]`
words, and `row_landing.scatter_entry` on the same words (the form of the
CPU backend).  At 100 / 300 / 3,072 live words of the plan's 3,072.  The
price of a landing is the slope between two trip counts (dispatch and
launch cancel).  Every form is checked against the int64 scatter (a
weighted checksum of the whole store after three trips) before it is timed.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import graphite_tpu  # noqa: E402,F401  (x64 + compile cache placement)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from graphite_tpu.memory.engine import _entry_rows  # noqa: E402
from graphite_tpu.memory.row_landing import (  # noqa: E402
    entry_int64, entry_words, land_entry, pack_entry_plan, scatter_entry,
)

I64 = jnp.int64
PHASES = 3


def counts(config_name):
    """One reading of a cell's configuration on the CPU backend with a
    host callback in front of every landing: the plan's live words (a
    nonzero delta that no earlier phase folded) an open iteration - a
    constant of the traffic."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from lib import target

    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.memory import row_landing

    seen = []
    apply_entry = row_landing.apply_entry

    def counted(store, sets, way, delta, live, **kw):
        jax.debug.callback(
            lambda n, f: seen.append((int(n), int(f))),
            jnp.sum(live & (delta != 0)), jnp.sum(~live), ordered=True)
        return apply_entry(store, sets, way, delta, live, **kw)

    row_landing.apply_entry = counted
    cfg = target.load_config(config_name)
    sim = Simulator(target.build_sim_config(cfg), target.build_trace(cfg),
                    **cfg["simulator"])
    d = sim.state.mem.directory
    print(f"{config_name}: entry store {d.entry.dtype}{list(d.entry.shape)}")
    t0 = time.perf_counter()
    sim.run()
    jax.effects_barrier()
    live = np.array([n for n, _ in seen])
    folded = np.array([f for _, f in seen])
    its = int(sim.last_n_iterations)
    print(f"one reading in {time.perf_counter() - t0:.1f} s (CPU): {its} "
          f"iterations, {len(seen)} landings (open iterations), "
          f"{its - len(seen)} closed")
    print(f"  live words a landing: mean {live.mean():.1f} median "
          f"{np.median(live):.0f} p95 {np.percentile(live, 95):.0f} max "
          f"{live.max()} sum {live.sum()}; landings with none "
          f"{int((live == 0).sum())}; folded words {folded.sum()}")
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"entry45_counts_{config_name}.json"),
              "w") as f:
        json.dump({"config": config_name, "iterations": its,
                   "live": live.tolist(), "folded": folded.tolist()}, f)
    return 0


def plan(shape, n_live, seed):
    """A plan as the three home phases leave one: `n_live` of the 3 * T
    words live, a lane's phases at distinct ways of random sets (so some
    share a tile), 64-bit deltas of either sign."""
    T, DS, DW = shape
    rng = np.random.default_rng(seed)
    sets = rng.integers(0, DS, (PHASES, T)).astype(np.int32)
    way = ((rng.integers(0, DW, T)[None, :] + np.arange(PHASES)[:, None])
           % DW).astype(np.int32)
    delta = rng.integers(-2**62, 2**62, (PHASES, T), dtype=np.int64)
    live = np.zeros(PHASES * T, bool)
    live[rng.permutation(PHASES * T)[:n_live]] = True
    return (jnp.asarray(sets), jnp.asarray(way), jnp.asarray(delta),
            jnp.asarray(live.reshape(PHASES, T)))


def rows_int64(store, sets):
    """The three gathered set rows a lane, int64[T, 3, DW], as the engine
    takes them out of a store of either form."""
    lanes = np.arange(store.shape[0], dtype=np.int32)[:, None]
    return _entry_rows(store, lanes, sets.T)


def xla_int64(store, sets, way, delta, live):
    T = store.shape[0]
    lanes = jnp.where(live, jnp.arange(T, dtype=jnp.int32)[None, :], T)
    return store.at[lanes.ravel(), sets.ravel(), way.ravel()].add(
        delta.ravel(), mode="drop", unique_indices=True)


def pack_alone(store, *plan):
    """The kernel's plan packing (the sort) without the kernel: its sums
    land on one word of the store, in place."""
    packed = pack_entry_plan(store, *plan)
    word = sum(jnp.sum(x, dtype=jnp.int32) for x in packed)
    # (a dynamic_update_slice, not a scatter: a scatter would pass over
    # the store)
    return jax.lax.dynamic_update_slice(
        store, store[:1, :1, :1] + word.astype(store.dtype), (0, 0, 0))


def looped(form, shape):
    """n landings in one program; trip i moves every word by i sets and
    bends each delta by the rows it gathered (so the gather is live)."""
    _, DS, _ = shape

    def run(store, sets, way, delta, live, n):
        def body(i, s):
            moved = (sets + i.astype(jnp.int32)) % DS
            rows = rows_int64(s, moved)
            bend = jnp.sum(rows, axis=2).T & 1
            return form(s, moved, way, delta + bend, live)
        return jax.lax.fori_loop(0, n, body, store)
    return jax.jit(run, donate_argnums=0)


def make_store(shape, words):
    T, DS, DW = shape

    @jax.jit
    def make():
        t = jax.lax.broadcasted_iota(I64, shape, 0)
        s = jax.lax.broadcasted_iota(I64, shape, 1)
        w = jax.lax.broadcasted_iota(I64, shape, 2)
        e = (t * 2246822519 + s * 2654435761 + w * 40503 + 7) * 1000003
        return entry_words(e) if words else e
    return make()


@jax.jit
def checksum(store):
    e = entry_int64(store)
    t = jax.lax.broadcasted_iota(I64, e.shape, 0)
    s = jax.lax.broadcasted_iota(I64, e.shape, 1)
    w = jax.lax.broadcasted_iota(I64, e.shape, 2)
    return jnp.sum(e * (t * 977 + s * 31 + w + 1))


def time_form(name, form, words, shape, n_live, seed, trips, repeats):
    tab = plan(shape, n_live, seed)
    run = looped(form, shape)
    store = make_store(shape, words)
    t0 = time.perf_counter()
    store = run(store, *tab, 3)
    digest = int(checksum(store))
    first = time.perf_counter() - t0
    walls = {}
    for n in trips:
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            store = run(store, *tab, n)
            store.block_until_ready()
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        walls[n] = best
    lo, hi = trips
    del store
    return {"form": name, "live": n_live,
            "landing_ms": 1e3 * (walls[hi] - walls[lo]) / (hi - lo),
            "digest": digest, "first_call_s": first,
            "walls_s": {str(k): v for k, v in walls.items()}}


def describe(forms, shape):
    """Real sizes through the TPU compiler for a described v5e: what it
    refuses here costs no chip time, and the loop body's operations on
    anything the size of a store half are what a landing will cost."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from graphite_tpu.analysis import loop_copies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    T, DS, DW = shape

    def sh(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    half = T * DS * DW
    for name, form, words in forms:
        store = (sh((T, 2 * DW, DS), jnp.uint32) if words
                 else sh((T, DS, DW), I64))
        t0 = time.perf_counter()
        try:
            c = looped(form, shape).lower(
                store, sh((PHASES, T), jnp.int32),
                sh((PHASES, T), jnp.int32), sh((PHASES, T), I64),
                sh((PHASES, T), jnp.bool_), sh((), jnp.int32)).compile()
        except Exception as e:  # noqa: BLE001 — report, go on
            print(f"{name}: REFUSED {str(e)[:1500]}")
            continue
        m = c.memory_analysis()
        text = c.as_text()
        print(f"{name}: ok in {time.perf_counter() - t0:.1f} s, temp "
              f"{m.temp_size_in_bytes}, alias {m.alias_size_in_bytes}, "
              f"custom-calls {text.count('tpu_custom_call')}")
        comps = loop_copies.computations(text)
        for loop in loop_copies.loops(comps).values():
            for comp in sorted(loop.comps):
                for ln in comps[comp]:
                    m = loop_copies._ARRAY.search(ln.split("=", 1)[-1])
                    if not m or " parameter(" in ln or "tuple(" in ln:
                        continue
                    dims = [int(d) for d in m.group(2).split(",") if d]
                    if int(np.prod(dims or [1])) >= half // 2 and (
                            "fusion(" in ln or " copy" in ln
                            or "reshape(" in ln or "slice" in ln
                            or "custom-call(" in ln or "bitcast(" in ln):
                        print("   ", ln.strip()[:200])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--counts", metavar="CONFIG",
                    help="count a reading's live words on the CPU")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--seed", type=int, default=45)
    args = ap.parse_args()
    if args.counts:
        return counts(args.counts)

    def kernel(s, *plan):
        return land_entry(s, *plan, interpret=args.rehearse)

    forms = [("XLA scatter-add, int64 store (today)", xla_int64, False),
             ("kernel, u32 words", kernel, True),
             ("XLA gather + set, u32 words", scatter_entry, True),
             ("the kernel's plan packing alone", pack_alone, True)]
    if args.rehearse:
        shape, lives, trips, repeats = (16, 128, 8), (5, 12, 48), (1, 2), 1
    else:
        shape, lives, trips, repeats = ((1024, 1024, 16),
                                        (0, 100, 300, 3072), (4, 20), 3)
    if args.describe:
        return describe(forms, shape)

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; entry store "
          f"int64{list(shape)} / u32[{shape[0]},{2 * shape[2]},{shape[1]}]"
          f"; trips {trips}, best of {repeats}")
    rows_out = []
    for n_live in lives:
        want = None
        for name, form, words in forms:
            try:
                row = time_form(name, form, words, shape, n_live, args.seed,
                                trips, repeats)
            except Exception as e:  # noqa: BLE001 — a form the chip refuses
                print(f"{name} {n_live}: FAILED {str(e)[:600]}")
                continue
            if want is None:
                want = row["digest"]
            row["equals_int64_scatter"] = (row["digest"] == want
                                           or form is pack_alone)
            rows_out.append(row)
            print(f"{name:38s} live {n_live:>5d}: "
                  f"{row['landing_ms']:9.4f} ms a landing "
                  f"equal={row['equals_int64_scatter']} "
                  f"(first call {row['first_call_s']:.1f} s)", flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "entry45.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "trips": trips, "rows": rows_out}, f, indent=1)
    return 1 if any(not r["equals_int64_scatter"] for r in rows_out) else 0


if __name__ == "__main__":
    sys.exit(main())
