"""PR 43, step 0. Run: `JAX_PLATFORMS=cpu python _hand/flush43.py --counts` (26 s), then `chiprun --chips 1 -- python _hand/flush43.py` (55 s held; 2026-10-01, TPU v5 lite).
Read: a reading of coh-1024-memstress has 38 live flushes of 2,148.5 staged slots (max 3,294), max(sn) 17.8 (max 32), 62% superseded; one flush alone: XLA 19.2-20.2 ms whatever was staged, the kernel 0.28 ms (2,161 slots) / 0.41 (3,577) / 8.87 (98,304) / 0.08 (none) - PERF.md section 6, PR 43.

What does ONE staging flush of the private-L2 directory cost, alone, and
what does a flush of `memstress1024-coh` hold?

    JAX_PLATFORMS=cpu python _hand/flush43.py --counts     # staged slots, max(sn), superseded share of every live flush of a reading
    JAX_PLATFORMS=cpu python _hand/flush43.py --rehearse   # tiny, kernel interpreted
    JAX_PLATFORMS=cpu python _hand/flush43.py --describe   # real sizes through the TPU compiler, no chip
    chiprun --chips 1 -- python _hand/flush43.py           # the table, on the chip

Times the XLA flush (`engine.dir_stage_flush` as the parent of PR 43 has
it) and `row_landing.land_staged` on a donated `u32[T*DS, DW*SW]` sharers
store with a `[T, C]` table filled as `--counts` says the cell fills it:
`hot` lanes carry `deep` slots each, the rest `shallow`.  Both run inside
one program, a `fori_loop` of n flushes whose sets move every trip, at two
trip counts; the price of a flush is the slope (dispatch and launch
cancel).  Every form is checked against the XLA flush (a weighted checksum
of the whole store after three flushes) before it is timed.
"""
import argparse
import functools
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

from graphite_tpu.memory.row_landing import (  # noqa: E402
    land_staged, scatter_staged,
)

U32 = jnp.uint32


# ---------------------------------------------------------------------------
# --counts: the cell's own flushes, on the CPU
# ---------------------------------------------------------------------------


def counts(config_name):
    """One reading of the cell's configuration on the CPU backend with a
    host callback in front of every flush: per LIVE flush the staged
    slots, max(sn), the lanes that staged anything and the slots that are
    not their key's last."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from lib import target

    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.memory import engine

    seen = []

    def record(live, sn, skey):
        if not live:
            seen.append(None)
            return
        sn, skey = np.asarray(sn), np.asarray(skey)
        superseded = 0
        for t in np.nonzero(sn > 1)[0]:
            keys = skey[t, :sn[t]]
            superseded += len(keys) - len(set(keys.tolist()))
        seen.append({"slots": int(sn.sum()), "max_sn": int(sn.max()),
                     "lanes": int((sn > 0).sum()),
                     "superseded": superseded})

    flush = engine.dir_stage_flush

    def counted(d, live=None, **kw):
        jax.debug.callback(record, jnp.asarray(True) if live is None
                           else live, d.sn, d.skey, ordered=True)
        return flush(d, live, **kw)

    engine.dir_stage_flush = counted
    cfg = target.load_config(config_name)
    sim = Simulator(target.build_sim_config(cfg), target.build_trace(cfg),
                    **cfg["simulator"])
    d = sim.state.mem.directory
    print(f"{config_name}: sharers {d.sharers.shape}, table "
          f"{d.skey.shape}, sval {d.sval.shape}, inner_block "
          f"{sim.params.inner_block}")
    t0 = time.perf_counter()
    sim.run()
    jax.effects_barrier()
    live = [s for s in seen if s]
    print(f"one reading in {time.perf_counter() - t0:.1f} s (CPU): "
          f"{int(sim.last_n_iterations)} iterations, {len(seen)} blocks, "
          f"{len(live)} live flushes")
    for key in ("slots", "max_sn", "lanes", "superseded"):
        v = [s[key] for s in live]
        print(f"  {key:10s} mean {np.mean(v):8.1f} median "
              f"{np.median(v):7.1f} max {max(v):6d} sum {sum(v)}")
    slots = sum(s["slots"] for s in live)
    print(f"  superseded share {100 * sum(s['superseded'] for s in live) / slots:.2f}% of {slots} staged slots")
    hist = np.bincount([s["max_sn"] for s in live])
    print("  max_sn histogram:", {i: int(n) for i, n in enumerate(hist) if n})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "flush43_counts.json"), "w") as f:
        json.dump({"config": config_name, "flushes": seen}, f)
    return 0


# ---------------------------------------------------------------------------
# the price table
# ---------------------------------------------------------------------------

# (T, DS, DW, SW, C) and how the table is filled: (lanes that staged a
# lot, their slots each [lo, hi], lanes that staged a little, theirs)
SHAPE = (1024, 1024, 16, 32, 96)
FILLS = {
    # --counts, coh-1024-memstress: a flush holds 2,148 slots (mean; 3,294
    # at most) in 590 lanes (843), max(sn) 18 (32), 62% of them superseded
    "typical": (100, (8, 20), 490, (1, 2)),
    "largest": (128, (12, 32), 715, (1, 1)),
    "full": (1024, (96, 96), 0, (0, 0)),
    "empty": (0, (0, 0), 0, (0, 0)),
}


def table(shape, fill, seed):
    """A staging table as the engine leaves one: a lane's first `sn`
    slots live, keys drawn from a small pool a lane (so ~60% of the slots
    are superseded by a later one), the rest -1."""
    T, DS, DW, SW, C = shape
    hot, (hlo, hhi), cold, (clo, chi) = fill
    rng = np.random.default_rng(seed)
    sn = np.zeros(T, np.int32)
    lanes = rng.permutation(T)
    sn[lanes[:hot]] = rng.integers(hlo, hhi + 1, hot)
    sn[lanes[hot:hot + cold]] = rng.integers(clo, chi + 1, cold)
    sn = np.minimum(sn, C)
    skey = np.full((T, C), -1, np.int32)
    for t in np.nonzero(sn)[0]:
        pool = rng.integers(0, DS * DW, max(1, int(0.4 * sn[t])))
        skey[t, :sn[t]] = rng.choice(pool, sn[t])
    sval = rng.integers(0, 2**32, (T, C, SW), dtype=np.uint32)
    return jnp.asarray(skey), jnp.asarray(sval), jnp.asarray(sn)


def xla_flush(sharers, skey, sval, sn):
    return scatter_staged(sharers, skey, sval)


def kernel(step, interpret=False):
    return functools.partial(land_staged, lanes_per_step=step,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnums=0)
def make_store(shape):
    t = jax.lax.broadcasted_iota(U32, shape, 0)
    r = jax.lax.broadcasted_iota(U32, shape, 1)
    c = jax.lax.broadcasted_iota(U32, shape, 2)
    return (t * U32(2246822519) + r * U32(2654435761) + c * U32(40503)
            + U32(7))


@jax.jit
def checksum(store):
    t = jax.lax.broadcasted_iota(U32, store.shape, 0)
    r = jax.lax.broadcasted_iota(U32, store.shape, 1)
    c = jax.lax.broadcasted_iota(U32, store.shape, 2)
    return jnp.sum(store * (t * U32(977) + r * U32(31) + c + U32(1)),
                   dtype=U32)


def looped(form, shape):
    """n flushes in one program; trip i shifts every key by i sets."""
    T, DS, DW, SW, C = shape

    def run(store, skey, sval, sn, n):
        def body(i, s):
            i = i.astype(jnp.int32)
            moved = jnp.where(skey >= 0, (skey + i * DW) % (DS * DW), -1)
            return form(s, moved, sval + i.astype(U32), sn)
        return jax.lax.fori_loop(0, n, body, store)
    return jax.jit(run, donate_argnums=0)


def time_form(name, form, shape, fill_name, seed, trips, repeats):
    T, DS, DW, SW, C = shape
    tab = table(shape, FILLS[fill_name], seed)
    run = looped(form, shape)
    store = make_store((T, DS, DW * SW))
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
    sn = np.asarray(tab[2])
    return {"form": name, "fill": fill_name, "slots": int(sn.sum()),
            "max_sn": int(sn.max()), "lanes": int((sn > 0).sum()),
            "flush_ms": 1e3 * (walls[hi] - walls[lo]) / (hi - lo),
            "digest": digest, "first_call_s": first,
            "walls_s": {str(k): v for k, v in walls.items()}}


def describe(forms, shape):
    """Real sizes through the TPU compiler for a described v5e: what it
    refuses here costs no chip time."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    T, DS, DW, SW, C = shape

    def sh(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for name, form in forms:
        t0 = time.perf_counter()
        try:
            c = looped(form, shape).lower(
                sh((T, DS, DW * SW), U32), sh((T, C), jnp.int32),
                sh((T, C, SW), U32), sh((T,), jnp.int32),
                sh((), jnp.int32)).compile()
        except Exception as e:  # noqa: BLE001 — report, go on
            print(f"{name}: REFUSED {str(e)[:1500]}")
            continue
        m = c.memory_analysis()
        text = c.as_text()
        big = (f"u32[{T},{DS},{DW * SW}]", f"u32[{T * DS},{DW * SW}]")
        copies = sum(1 for ln in text.splitlines() if " copy(" in ln
                     and any(b in ln.split("=")[-1][:40] for b in big))
        print(f"{name}: ok in {time.perf_counter() - t0:.1f} s, temp "
              f"{m.temp_size_in_bytes}, alias {m.alias_size_in_bytes}, "
              f"copies of the store {copies}, custom-calls "
              f"{text.count('tpu_custom_call')}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--counts", action="store_true")
    ap.add_argument("--config", default="coh-1024-memstress")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--seed", type=int, default=43)
    args = ap.parse_args()
    if args.counts:
        return counts(args.config)

    interpret = args.rehearse
    forms = [("XLA flush (today)", xla_flush),
             ("kernel, 1024 lanes a step", kernel(1024, interpret)),
             ("kernel, 256 lanes a step", kernel(256, interpret))]
    if args.rehearse:
        shape, trips, repeats = (16, 16, 8, 16, 12), (1, 2), 1
        FILLS.update(typical=(4, (3, 8), 8, (1, 2)),
                     largest=(6, (6, 12), 10, (1, 1)),
                     full=(16, (12, 12), 0, (0, 0)))
        forms[1:] = [("kernel, 16 lanes a step", kernel(16, True)),
                     ("kernel, 8 lanes a step", kernel(8, True))]
    else:
        shape, trips, repeats = SHAPE, (4, 20), 3
    if args.describe:
        return describe(forms, shape)

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; store "
          f"u32[{shape[0] * shape[1]},{shape[2] * shape[3]}], table "
          f"[{shape[0]},{shape[4]}]; trips {trips}, best of {repeats}")
    rows_out = []
    for fill_name in FILLS:
        want = None
        for name, form in forms:
            try:
                row = time_form(name, form, shape, fill_name, args.seed,
                                trips, repeats)
            except Exception as e:  # noqa: BLE001 — a form the chip refuses
                print(f"{name} {fill_name}: FAILED {str(e)[:600]}")
                continue
            if want is None:
                want = row["digest"]
            row["equals_xla_flush"] = row["digest"] == want
            rows_out.append(row)
            print(f"{name:28s} {fill_name:8s} slots {row['slots']:>6d} "
                  f"max(sn) {row['max_sn']:>3d} lanes {row['lanes']:>5d}: "
                  f"{row['flush_ms']:9.4f} ms a flush "
                  f"equal={row['equals_xla_flush']} "
                  f"(first call {row['first_call_s']:.1f} s)", flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "flush43.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "trips": trips, "rows": rows_out}, f, indent=1)
    return 1 if any(not r["equals_xla_flush"] for r in rows_out) else 0


if __name__ == "__main__":
    sys.exit(main())
