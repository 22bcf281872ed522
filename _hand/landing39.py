"""PR 39, step 0: what does ONE landing of a row plan on the shared-L2
sharers store cost, alone, and does the price follow the rows or the store?

Times each form of `store.at[rows].add(delta)` (rows unique and sorted, one
per [S, W] slab, as `engine_shl2._dir_apply_rows` lands them) on a donated
`u32[N, W]` store, inside one program: a `fori_loop` of n landings whose
sets move every trip, at two trip counts; the price of a landing is the
slope (dispatch and launch cancel).  Cases: today's store at 1,024 and at
128 rows, and a store an eighth the size at 1,024 rows.  Time that falls
with the rows -> element/row-serial; with the store -> a pass over the
operand.

    chiprun --chips 1 -- python _hand/landing39.py     # the table, on the chip
    JAX_PLATFORMS=cpu python _hand/landing39.py --rehearse   # tiny, kernel interpreted
    JAX_PLATFORMS=cpu python _hand/landing39.py --describe   # real sizes through the TPU compiler, no chip

Every form is checked against today's scatter-add (a weighted checksum of
the whole store after three landings) before it is timed.
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

from graphite_tpu.memory.row_landing import land_rows  # noqa: E402

U32 = jnp.uint32
KW = dict(unique_indices=True, indices_are_sorted=True)
PIB = dict(KW, mode="promise_in_bounds")


def scatter_add(store, rows, delta):
    return store.at[rows].add(delta, **KW)


def scatter_add_pib(store, rows, delta):
    return store.at[rows].add(delta, **PIB)


def gather_set(store, rows, delta):
    return store.at[rows].set(store[rows] + delta, **KW)


def gather_set_pib(store, rows, delta):
    got = store.at[rows].get(**PIB)
    return store.at[rows].set(got + delta, **PIB)


def dus_loop(store, rows, delta):
    """Row-serial by construction: one dynamic-update-slice a row."""
    def body(r, s):
        at = (rows[r], jnp.int32(0))
        row = jax.lax.dynamic_slice(s, at, (1, s.shape[1]))
        new = row + jax.lax.dynamic_slice_in_dim(delta, r, 1)
        return jax.lax.dynamic_update_slice(s, new, at)
    return jax.lax.fori_loop(0, rows.shape[0], body, store)


def kernel(step, interpret=False):
    return functools.partial(land_rows, rows_per_step=step,
                             interpret=interpret)


def gather_only(store, rows, delta):
    """Not a landing: the sparse READ of the same rows, folded into one
    word of the store so the loop cannot drop it."""
    word = jnp.sum(store[rows] ^ delta, dtype=U32)
    return jax.lax.dynamic_update_slice(store, word[None, None],
                                        (jnp.int32(0), jnp.int32(0)))


def plan(case, seed):
    n_store, width, n_rows = case
    slab = n_store // n_rows
    k1, k2 = jax.random.split(jax.random.key(seed))
    sets = jax.random.randint(k1, (n_rows,), 0, slab, jnp.int32)
    delta = jax.random.bits(k2, (n_rows, width), U32)
    return sets, delta


@functools.partial(jax.jit, static_argnums=0)
def make_store(shape):
    r = jax.lax.broadcasted_iota(U32, shape, 0)
    c = jax.lax.broadcasted_iota(U32, shape, 1)
    return r * U32(2654435761) + c * U32(40503) + U32(7)


@jax.jit
def checksum(store):
    r = jax.lax.broadcasted_iota(U32, store.shape, 0)
    c = jax.lax.broadcasted_iota(U32, store.shape, 1)
    return jnp.sum(store * (r * U32(31) + c + U32(1)), dtype=U32)


def looped(form, slab):
    """n landings in one program; trip i lands on sets + i (mod slab)."""
    def run(store, sets, delta, n):
        lt = jnp.arange(sets.shape[0], dtype=jnp.int32)

        def body(i, s):
            rows = lt * slab + (sets + i.astype(jnp.int32)) % slab
            return form(s, rows, delta + i.astype(U32))
        return jax.lax.fori_loop(0, n, body, store)
    return jax.jit(run, donate_argnums=0)


def time_form(name, form, case, seed, trips, repeats):
    n_store, width, n_rows = case
    slab = n_store // n_rows
    sets, delta = plan(case, seed)
    run = looped(form, slab)
    store = make_store((n_store, width))
    t0 = time.perf_counter()
    store = run(store, sets, delta, 3)
    digest = int(checksum(store))
    compile_s = time.perf_counter() - t0
    walls = {}
    for n in trips:
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            store = run(store, sets, delta, n)
            store.block_until_ready()
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        walls[n] = best
    lo, hi = trips
    ms = 1e3 * (walls[hi] - walls[lo]) / (hi - lo)
    del store
    return {"form": name, "store": [n_store, width], "rows": n_rows,
            "landing_ms": ms, "digest": digest,
            "walls_s": {str(k): v for k, v in walls.items()},
            "first_call_s": compile_s}


def describe(forms, cases):
    """Real sizes through the TPU compiler for a described v5e: what it
    refuses here costs no chip time."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)

    def sh(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for case in cases:
        n_store, width, n_rows = case
        for name, form in forms:
            t0 = time.perf_counter()
            try:
                c = looped(form, n_store // n_rows).lower(
                    sh((n_store, width), U32), sh((n_rows,), jnp.int32),
                    sh((n_rows, width), U32),
                    sh((), jnp.int32)).compile()
            except Exception as e:  # noqa: BLE001 — report, go on
                print(f"{name} {case}: REFUSED {str(e)[:300]}")
                continue
            m = c.memory_analysis()
            text = c.as_text()
            copies = sum(1 for ln in text.splitlines()
                         if f"u32[{n_store},{width}]" in ln.split("=")[-1][:40]
                         and " copy(" in ln)
            print(f"{name} {case}: ok in {time.perf_counter() - t0:.1f} s, "
                  f"temp {m.temp_size_in_bytes}, alias {m.alias_size_in_bytes},"
                  f" copies of the store {copies}, "
                  f"custom-calls {text.count('tpu_custom_call')}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--seed", type=int, default=39)
    args = ap.parse_args()

    interpret = args.rehearse
    forms = [
        ("scatter_add (today)", scatter_add),
        ("scatter_add promise_in_bounds", scatter_add_pib),
        ("gather + scatter-set", gather_set),
        ("gather + scatter-set promise_in_bounds", gather_set_pib),
        ("kernel, 256 rows a step", kernel(256, interpret)),
        ("kernel, 512 rows a step", kernel(512, interpret)),
        ("kernel, 1024 rows a step", kernel(1024, interpret)),
        ("dynamic-update-slice a row", dus_loop),
        ("(gather of the rows only)", gather_only),
    ]
    if args.rehearse:
        cases = [(64 * 16, 128, 16), (64 * 16, 128, 8), (16 * 16, 128, 16)]
        trips, repeats = (1, 2), 1
    else:
        cases = [(1048576, 256, 1024), (1048576, 256, 128),
                 (131072, 256, 1024)]
        trips, repeats = (8, 40), 3
    if args.describe:
        return describe(forms, cases)

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; trips {trips}, "
          f"best of {repeats}")
    rows_out = []
    for case in cases:
        want = None
        for name, form in forms:
            try:
                row = time_form(name, form, case, args.seed, trips, repeats)
            except Exception as e:  # noqa: BLE001 — a form the chip refuses
                print(f"{name} {case}: FAILED {str(e)[:400]}")
                continue
            if want is None:
                want = row["digest"]
            row["equals_scatter_add"] = (row["digest"] == want
                                         if "only" not in name else None)
            rows_out.append(row)
            print(f"{name:42s} store {case[0]:>8d}x{case[1]} rows "
                  f"{case[2]:>5d}: {row['landing_ms']:9.4f} ms a landing "
                  f"equal={row['equals_scatter_add']} "
                  f"(first call {row['first_call_s']:.1f} s)", flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "landing39.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "trips": trips, "rows": rows_out}, f, indent=1)
    bad = [r for r in rows_out if r["equals_scatter_add"] is False]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
