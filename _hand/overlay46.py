"""PR 46, step 0. Run: `chiprun --chips 1 -- python _hand/overlay46.py` (numbers: PERF.md section 6, PR 46).

What does the staging overlay of the directory's working set cost an open
iteration, alone, and what would fetching a staged value for the way a
phase reads cost instead?

    JAX_PLATFORMS=cpu python _hand/overlay46.py --counts coh-1024-memstress   # how often a read way has a staged slot
    JAX_PLATFORMS=cpu python _hand/overlay46.py --rehearse   # tiny, every form against the eager overlay
    JAX_PLATFORMS=cpu python _hand/overlay46.py --describe   # real sizes through the TPU compiler, no chip: the loop body's gathers and relayouts
    chiprun --chips 1 -- python _hand/overlay46.py           # the table, on the chip

Times, in a `fori_loop` of n trips over a donated staging table `(skey,
sval, sn)` of the cell's size (T 1024, C 96, SW 32) beside the 2 GB sharers
store, with sets and ways that move every trip and one `_stage_put` a trip
(so the table is carried and written as the engine's is):

  (a) today's overlay: `sharers[lt, sets]` + `engine._stage_overlay_rows`
      over all `[T, 3, DW * SW]` rows, then the four `[T, SW]` values the
      home phases read (one way a view, the third view twice);
  (b) the index alone: the store's rows + `best` / `has` from `skey`, no
      value out of `sval`;
  (c) the index + four fetches of `[T]` rows of `sval` at the read ways.

The price of a trip is the slope between two trip counts (dispatch and
launch cancel).  (c)'s four values are checked against (a)'s before
anything is timed (a checksum over three trips).
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

from graphite_tpu.memory import engine  # noqa: E402
from graphite_tpu.memory.state import DirectoryArrays  # noqa: E402

U32 = jnp.uint32
K = 3        # gathered set rows a lane
READS = 4    # [T, SW] values read an open iteration: one a view, one more


def counts(config_name):
    """One reading of a staged configuration on the CPU backend with a
    host callback behind every value fetch of a view: how many of the T
    lanes' read ways had a staged slot - a constant of the traffic."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from lib import target

    from graphite_tpu.engine.simulator import Simulator

    seen = []
    fetch = engine._stage_fetch

    def counted(sval, best):
        jax.debug.callback(lambda n: seen.append(int(n)), jnp.sum(best > 0),
                           ordered=True)
        return fetch(sval, best)

    engine._stage_fetch = counted
    cfg = target.load_config(config_name)
    sim = Simulator(target.build_sim_config(cfg), target.build_trace(cfg),
                    **cfg["simulator"])
    d = sim.state.mem.directory
    T = d.skey.shape[0]
    print(f"{config_name}: staging table skey {list(d.skey.shape)} sval "
          f"{list(d.sval.shape)}")
    t0 = time.perf_counter()
    sim.run()
    jax.effects_barrier()
    hit = np.array(seen)
    its = int(sim.last_n_iterations)
    closed = int(sim.last_base_skips["base"])
    print(f"one reading in {time.perf_counter() - t0:.1f} s (CPU): {its} "
          f"iterations, {its - closed} open; {len(hit)} fetches = "
          f"{len(hit) / max(its - closed, 1):.2f} an open iteration")
    print(f"  lanes whose read way has a staged slot, a fetch: mean "
          f"{hit.mean():.1f} of {T} ({100 * hit.mean() / T:.2f}%), median "
          f"{np.median(hit):.0f}, p95 {np.percentile(hit, 95):.0f}, max "
          f"{hit.max()}; fetches with none {int((hit == 0).sum())}")
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"overlay46_counts_{config_name}.json"),
              "w") as f:
        json.dump({"config": config_name, "iterations": its,
                   "open": its - closed, "lanes": T,
                   "staged_lanes_a_fetch": hit.tolist()}, f)
    return 0


def table(shape, seed):
    """A staging table as a block leaves one half way: 0..32 valid slots
    a lane (the cell's `max(sn)` is 17.8 mean, 32 max), keys drawn from a
    few sets so they repeat in a lane, -1 beyond the cursor."""
    T, DS, DW, SW, C = shape
    rng = np.random.default_rng(seed)
    sn = rng.integers(0, min(33, C // 2), T).astype(np.int32)
    sets = rng.integers(0, DS, (T, K)).astype(np.int32)
    way = rng.integers(0, DW, (T, READS)).astype(np.int32)
    pick = rng.integers(0, K, (T, C))
    key = (np.take_along_axis(sets, pick, 1) * DW
           + rng.integers(0, DW, (T, C))).astype(np.int32)
    skey = np.where(np.arange(C)[None, :] < sn[:, None], key, -1)
    sval = rng.integers(0, 2**32, (T, C, SW), dtype=np.uint32)
    return (jnp.asarray(skey, jnp.int32), jnp.asarray(sval), jnp.asarray(sn),
            jnp.asarray(sets), jnp.asarray(way))


def _index(d, sets):
    """int32[T, K, DW]: 1 + the latest staged slot of each (lane, set, way),
    0 where none - the eager overlay's compare and max over C, alone."""
    return engine._stage_index(d, sets, d.sharers.shape[2] // d.sval.shape[2])


_fetch = engine._stage_fetch    # [T, SW] of slot `best`, zeros where none


def _unstaged(sh, best):
    """The store's rows with every way that has a staged slot zeroed:
    what a read adds its fetched value to."""
    T, k, _ = sh.shape
    DW = best.shape[2]
    sh4 = sh.reshape(T, k, DW, -1)
    return jnp.where((best > 0)[..., None], U32(0), sh4).reshape(sh.shape)


def _at(rows3, way):
    return jnp.take_along_axis(rows3, way[:, None, None], axis=1)[:, 0]


def eager(d, sets, way):
    """(a): every way of every gathered row overlaid, then read."""
    T = sets.shape[0]
    lt = np.arange(T, dtype=np.int32)[:, None]
    sh = engine._stage_overlay_rows(d, sets, d.sharers[lt, sets])
    SW = d.sval.shape[2]
    vals = [_at(sh[:, min(r, K - 1)].reshape(T, -1, SW), way[:, r])
            for r in range(READS)]
    # the whole rows are live in the engine (forwarded, carried into the
    # phases' conds): keep them live here
    return vals, jnp.sum(sh, axis=(1, 2), dtype=U32)


def index_alone(d, sets, way):
    """(b): the store's rows and the index, no value out of `sval`."""
    T = sets.shape[0]
    lt = np.arange(T, dtype=np.int32)[:, None]
    best = _index(d, sets)
    sh = _unstaged(d.sharers[lt, sets], best)
    SW = d.sval.shape[2]
    vals = [_at(sh[:, min(r, K - 1)].reshape(T, -1, SW), way[:, r])
            for r in range(READS)]
    return vals, (jnp.sum(sh, axis=(1, 2), dtype=U32)
                  + jnp.sum(best, axis=(1, 2)).astype(U32))


def lazy(d, sets, way):
    """(c): the index, and a staged value fetched for each read way."""
    T = sets.shape[0]
    lt = np.arange(T, dtype=np.int32)[:, None]
    best = _index(d, sets)
    sh = _unstaged(d.sharers[lt, sets], best)
    SW = d.sval.shape[2]
    vals = []
    for r in range(READS):
        k = min(r, K - 1)
        b = jnp.take_along_axis(best[:, k], way[:, r][:, None], axis=1)[:, 0]
        vals.append(_at(sh[:, k].reshape(T, -1, SW), way[:, r])
                    + _fetch(d.sval, b))
    return vals, (jnp.sum(sh, axis=(1, 2), dtype=U32)
                  + jnp.sum(best, axis=(1, 2)).astype(U32))


def looped(form, shape):
    """n overlays in one program; trip i moves every set and way by i and
    appends one slot a lane in half the lanes, as a home phase would."""
    _, DS, DW, _, _ = shape

    def run(sharers, skey, sval, sn, sets, way, n):
        def body(i, carry):
            skey, sval, sn, acc, live = carry
            i = i.astype(jnp.int32)
            s = (sets + i) % DS
            w = (way + i) % DW
            d = DirectoryArrays(entry=None, sharers=sharers, skey=skey,
                                sval=sval, sn=sn)
            vals, rest = form(d, s, w)
            for r, v in enumerate(vals):
                acc = acc * U32(31) + v + U32(r)
            live = live + rest
            mask = ((np.arange(skey.shape[0]) & 1) == 0) ^ ((i & 1) == 1)
            # under a cond, as a home phase's puts are: a table that goes
            # through conditionals stays in HBM, as the cells' does (alone
            # in a loop the compiler keeps all of it in fast memory and
            # every gather from it costs less than half)
            def put(t):
                t = engine._stage_put(t, s[:, 0], w[:, 0], mask,
                                      vals[0] + U32(1), DW)
                return t.skey, t.sval, t.sn

            skey, sval, sn = jax.lax.cond(
                jnp.any(vals[0] != 0), put,
                lambda t: (t.skey, t.sval, t.sn), d.replace(sharers=None))
            return skey, sval, sn, acc, live
        T, _, SW = sval.shape
        zero = jnp.zeros((T, SW), U32)
        skey, sval, sn, acc, live = jax.lax.fori_loop(
            0, n, body, (skey, sval, sn, zero, jnp.zeros(T, U32)))
        return skey, sval, sn, acc, live
    return jax.jit(run, donate_argnums=(1, 2, 3))


def make_store(shape):
    T, DS, DW, SW, _ = shape

    @jax.jit
    def make():
        full = (T, DS, DW * SW)
        t = jax.lax.broadcasted_iota(U32, full, 0)
        s = jax.lax.broadcasted_iota(U32, full, 1)
        w = jax.lax.broadcasted_iota(U32, full, 2)
        return (t * U32(2246822519) + s * U32(2654435761)
                + w * U32(40503) + U32(7))
    return make()


def time_form(name, form, store, shape, seed, trips, repeats):
    skey, sval, sn, sets, way = table(shape, seed)
    run = looped(form, shape)
    t0 = time.perf_counter()
    skey, sval, sn, acc, live = run(store, skey, sval, sn, sets, way, 3)
    digest = int(jnp.sum(acc.astype(jnp.uint64) * 977 + 1))
    first = time.perf_counter() - t0
    walls = {}
    for n in trips:
        best = None
        for _ in range(repeats):
            # a fresh table a repeat: the cursors stay inside the table
            skey, sval, sn, _, _ = table(shape, seed)
            jax.block_until_ready((skey, sval, sn))
            t0 = time.perf_counter()
            out = run(store, skey, sval, sn, sets, way, n)
            jax.block_until_ready(out)
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        walls[n] = best
    lo, hi = trips
    return {"form": name, "trip_ms": 1e3 * (walls[hi] - walls[lo]) / (hi - lo),
            "digest": digest, "first_call_s": first,
            "walls_s": {str(k): v for k, v in walls.items()}}


def describe(forms, shape):
    """Real sizes through the TPU compiler for a described v5e: the loop
    body's gathers, and every operation there on something the size of
    the staging table or of the overlay's output."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from graphite_tpu.analysis import loop_copies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    T, DS, DW, SW, C = shape

    def sh(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    floor = T * SW * K
    for name, form in forms:
        t0 = time.perf_counter()
        c = looped(form, shape).lower(
            sh((T, DS, DW * SW), U32), sh((T, C), jnp.int32),
            sh((T, C, SW), U32), sh((T,), jnp.int32),
            sh((T, K), jnp.int32), sh((T, READS), jnp.int32),
            sh((), jnp.int32)).compile()
        m = c.memory_analysis()
        text = c.as_text()
        print(f"{name}: ok in {time.perf_counter() - t0:.1f} s, temp "
              f"{m.temp_size_in_bytes}, alias {m.alias_size_in_bytes}")
        comps = loop_copies.computations(text)
        for loop in loop_copies.loops(comps).values():
            for comp in sorted(loop.comps):
                if "fused_computation" in comp:
                    continue
                for ln in comps[comp]:
                    m = loop_copies._ARRAY.search(ln.split("=", 1)[-1])
                    if not m or " parameter(" in ln or "tuple(" in ln:
                        continue
                    dims = [int(x) for x in m.group(2).split(",") if x]
                    big = int(np.prod(dims or [1])) >= floor
                    op = any(k in ln for k in (" fusion(", " copy(",
                                               " gather(", " reshape(",
                                               " transpose("))
                    if op and (big or 'op_name="gather"' in ln
                               or "/gather\"" in ln):
                        print("   ", ln.strip().split(", backend_config")[0]
                              [:260])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--counts", metavar="CONFIG",
                    help="count a reading's staged read ways on the CPU")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--seed", type=int, default=46)
    args = ap.parse_args()
    if args.counts:
        return counts(args.counts)

    forms = [("(a) eager overlay of [T,3,DW*SW] rows (today)", eager),
             ("(b) the index alone", index_alone),
             ("(c) the index + four [T]-row fetches", lazy)]
    if args.rehearse:
        shape, trips, repeats = (16, 32, 4, 2, 24), (1, 2), 1
    else:
        shape, trips, repeats = (1024, 1024, 16, 32, 96), (4, 20), 3
    if args.describe:
        return describe(forms, shape)

    dev = jax.devices()[0]
    T, DS, DW, SW, C = shape
    print(f"device {dev.platform} {dev.device_kind}; sharers u32[{T},{DS},"
          f"{DW * SW}], sval u32[{T},{C},{SW}]; trips {trips}, best of "
          f"{repeats}")
    store = make_store(shape)
    rows_out = []
    want = None
    for name, form in forms:
        row = time_form(name, form, store, shape, args.seed, trips, repeats)
        if form is eager:
            want = row["digest"]
        row["equals_eager"] = row["digest"] == want or form is index_alone
        rows_out.append(row)
        print(f"{name:48s}: {row['trip_ms']:9.4f} ms a trip "
              f"equal={row['equals_eager']} "
              f"(first call {row['first_call_s']:.1f} s)", flush=True)
    a, c = rows_out[0]["trip_ms"], rows_out[2]["trip_ms"]
    print(f"(a) - (c) = {a - c:.4f} ms a trip; the gate is 0.25")
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "overlay46.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "trips": trips, "rows": rows_out}, f, indent=1)
    return 1 if any(not r["equals_eager"] for r in rows_out) else 0


if __name__ == "__main__":
    sys.exit(main())
