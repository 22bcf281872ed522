"""PR 52, step 0. Run: `chiprun --chips 1 -- python _hand/fold52.py` (numbers: PERF.md section 6, PR 52).

What do the staged directory's two landings cost under a campaign's sim
axis, alone, at `vfsweep256-canneal`'s shapes (B = 4 sims of T = 256 tiles)?

    JAX_PLATFORMS=cpu python _hand/fold52.py --rehearse   # tiny, kernels interpreted
    JAX_PLATFORMS=cpu python _hand/fold52.py --describe   # real sizes through the TPU compiler, no chip: the loop body's store-sized operations
    chiprun --chips 1 -- python _hand/fold52.py           # the table, on the chip
    JAX_PLATFORMS=cpu python _hand/fold52.py --lowered . ; JAX_PLATFORMS=cpu python _hand/fold52.py --lowered _proof/parent52
    python _hand/fold52.py --compare parent52 repo        # solo programs: the parent's text but for names? (no chip)

Both landings - the home phases' entry-word plan on `u32[4,256,32,1024]`
(`[4, 3, 256]` words, a few hundred live) and the staging flush on
`u32[4,256,1024,128]` (`[4, 256, 96]` slots of 8 words) - in three forms:

  (a) `vmap` of the XLA form (`scatter_entry` / `scatter_staged`): what a
      served batch ran before PR 52;
  (b) the kernel (`land_entry` / `land_staged`) on the operands folded BY
      HAND to 1,024 lanes;
  (c) `vmap` of the entry point (`apply_entry` / `flush_staged`): the
      batching rule of PR 52 - on a TPU it should be (b), to the letter.

Each runs under `engine._run_if` inside a `fori_loop` of n trips that
CARRIES the `[B, T, ...]` store (the aliasing is the point) and moves the
sets every trip; the price of a landing is the slope between two trip
counts.  Every form is checked against (a) - a weighted checksum of the whole
store after three trips - before it is timed.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "chiprun_out")
if "--lowered" in sys.argv:     # the programs of THAT checkout (the parent's)
    ROOT = os.path.abspath(sys.argv[sys.argv.index("--lowered") + 1])
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import graphite_tpu  # noqa: E402,F401  (x64 + compile cache placement)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from graphite_tpu.memory.engine import _run_if  # noqa: E402
from graphite_tpu.memory.row_landing import (  # noqa: E402
    apply_entry, flush_staged, land_entry, land_staged, scatter_entry,
    scatter_staged,
)

PHASES = 3
U32 = jnp.uint32


def entry_plan(shape, n_live, seed):
    """`[B, 3, T]` plans as the home phases leave them: `n_live` of the
    B * 3 * T words live, a lane's phases at distinct ways of random sets."""
    B, T, DS, DW = shape
    rng = np.random.default_rng(seed)
    sets = rng.integers(0, DS, (B, PHASES, T)).astype(np.int32)
    way = ((rng.integers(0, DW, (B, 1, T)) + np.arange(PHASES)[None, :, None])
           % DW).astype(np.int32)
    delta = rng.integers(-2**62, 2**62, (B, PHASES, T), dtype=np.int64)
    live = np.zeros(B * PHASES * T, bool)
    live[rng.permutation(live.size)[:n_live]] = True
    return tuple(jnp.asarray(x) for x in (
        sets, way, delta, live.reshape(B, PHASES, T)))


def stage_table(shape, n_lanes, depth, seed):
    """`[B, T, C]` staging tables as the engine leaves them: `n_lanes` of
    the B * T lanes hold 1..`depth` slots, keys from a small pool a lane
    (so a key repeats and its LATEST slot wins), the rest -1."""
    B, T, DS, DW, SW, C = shape
    rng = np.random.default_rng(seed)
    sn = np.zeros(B * T, np.int32)
    sn[rng.permutation(B * T)[:n_lanes]] = rng.integers(
        1, depth + 1, n_lanes)
    skey = np.full((B * T, C), -1, np.int32)
    for t in np.nonzero(sn)[0]:
        pool = rng.integers(0, DS * DW, max(1, int(0.4 * sn[t])))
        skey[t, :sn[t]] = rng.choice(pool, sn[t])
    sval = rng.integers(0, 2**32, (B, T, C, SW), dtype=np.uint32)
    return (jnp.asarray(skey.reshape(B, T, C)), jnp.asarray(sval),
            jnp.asarray(sn.reshape(B, T)))


def fold(x, axis=0):
    """`[B, ...]` with the sim axis merged into the lane axis `axis + 1`
    of the `[B, ...]` operand (= axis `axis` of a sim's), by hand."""
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(*x.shape[:axis], -1, *x.shape[axis + 2:])


def entry_forms(interpret):
    def by_hand(store, *plan):
        return land_entry(fold(store), *(fold(x, 1) for x in plan),
                          interpret=interpret).reshape(store.shape)

    return [("(a) vmap(scatter_entry)", jax.vmap(scatter_entry)),
            ("(b) land_entry, folded by hand", by_hand),
            ("(c) vmap(apply_entry)", jax.vmap(apply_entry))]


def stage_forms(interpret):
    def by_hand(sharers, skey, sval, sn):
        return land_staged(fold(sharers), fold(skey), fold(sval), fold(sn),
                           interpret=interpret).reshape(sharers.shape)

    return [("(a) vmap(scatter_staged)",
             lambda sharers, skey, sval, sn: jax.vmap(scatter_staged)(
                 sharers, skey, sval)),
            ("(b) land_staged, folded by hand", by_hand),
            ("(c) vmap(flush_staged)", jax.vmap(flush_staged))]


def looped_entry(form, shape):
    DS = shape[2]

    def run(store, sets, way, delta, live, n):
        def body(i, s):
            moved = (sets + i.astype(jnp.int32)) % DS
            return _run_if(i >= 0, lambda s: form(s, moved, way, delta, live),
                           s)
        return jax.lax.fori_loop(0, n, body, store)
    return jax.jit(run, donate_argnums=0)


def looped_stage(form, shape):
    _, _, DS, DW, _, _ = shape

    def run(store, skey, sval, sn, n):
        def body(i, s):
            moved = jnp.where(
                skey >= 0, (skey + i.astype(jnp.int32) * DW) % (DS * DW), -1)
            return _run_if(i >= 0, lambda s: form(s, moved, sval, sn), s)
        return jax.lax.fori_loop(0, n, body, store)
    return jax.jit(run, donate_argnums=0)


def make_store(shape):
    @jax.jit
    def make():
        word = jnp.zeros(shape, U32) + U32(7)
        for axis, mult in enumerate((2246822519, 2654435761, 40503, 1000003)):
            word = word + (jax.lax.broadcasted_iota(U32, shape, axis)
                           * U32(mult))
        return word
    return make()


@jax.jit
def checksum(store):
    weight = sum(jax.lax.broadcasted_iota(jnp.int64, store.shape, axis) * m
                 for axis, m in enumerate((977, 31, 7, 1)))
    return jnp.sum(store.astype(jnp.int64) * (weight + 1))


def time_form(name, run, store_shape, operands, trips, repeats):
    store = make_store(store_shape)
    t0 = time.perf_counter()
    store = run(store, *operands, 3)
    digest = int(checksum(store))
    first = time.perf_counter() - t0
    walls = {}
    for n in trips:
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            store = run(store, *operands, n)
            store.block_until_ready()
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        walls[n] = best
    lo, hi = trips
    del store
    return {"form": name,
            "landing_ms": 1e3 * (walls[hi] - walls[lo]) / (hi - lo),
            "digest": digest, "first_call_s": first,
            "walls_s": {str(k): v for k, v in walls.items()}}


def store_sized(text, n_words):
    """The loop bodies' operations on anything at least half a store."""
    from graphite_tpu.analysis import loop_copies

    comps = loop_copies.computations(text)
    found = []
    for loop in loop_copies.loops(comps).values():
        for comp in sorted(loop.comps):
            for ln in comps[comp]:
                m = loop_copies._ARRAY.search(ln.split("=", 1)[-1])
                if not m or " parameter(" in ln or "tuple(" in ln:
                    continue
                dims = [int(d) for d in m.group(2).split(",") if d]
                if int(np.prod(dims or [1])) >= n_words // 2 and any(
                        k in ln for k in ("fusion(", " copy", "reshape(",
                                          "slice", "custom-call(",
                                          "bitcast(")):
                    found.append(ln.strip()[:200])
    return found


def describe(cases):
    """Real sizes through the TPU compiler for a described v5e: what it
    refuses here costs no chip time, and a `copy` / `fusion` the size of
    the store in a loop body is a pass a landing will pay."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    for what, store_shape, looped, forms, operands in cases:
        args = [jax.ShapeDtypeStruct(store_shape, U32, sharding=one)] + [
            jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
            for x in operands] + [
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one)]
        for name, form in forms:
            t0 = time.perf_counter()
            try:
                c = looped(form).lower(*args).compile()
            except Exception as e:  # noqa: BLE001 — report, go on
                print(f"{what} {name}: REFUSED {str(e)[:1500]}")
                continue
            m = c.memory_analysis()
            text = c.as_text()
            print(f"{what} {name}: ok in {time.perf_counter() - t0:.1f} s, "
                  f"temp {m.temp_size_in_bytes}, alias "
                  f"{m.alias_size_in_bytes}, custom-calls "
                  f"{text.count('tpu_custom_call')}")
            for ln in store_sized(text, int(np.prod(store_shape))):
                print("   ", ln)


def lowered(checkout):
    """Four 16-tile programs of the checkout on `sys.path`, lowered for a
    TPU, as text under chiprun_out/: the staged solo target of
    tests/test_row_landing.py (`_entry_sim`: the flush takes the scatter,
    the entry words the kernel), the same with a directory both kernels
    take, and a served batch of two, unstaged (as `campaign64-dram` is)
    and staged."""
    from jax._src import core

    from graphite_tpu.analysis.audit import spec_from_sweep
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.sweep.runner import SweepRunner
    from graphite_tpu.tools._template import config_text
    from graphite_tpu.trace.synthetic import memory_stress_trace

    assert os.path.abspath(graphite_tpu.__file__).startswith(ROOT + os.sep)

    def trace(seed):
        return memory_stress_trace(
            16, n_accesses=24, working_set_bytes=8192, write_fraction=0.4,
            shared_fraction=0.5, seed=seed)

    text = config_text(16, core="simple", shared_mem=True,
                       clock_scheme="lax_barrier")
    narrow = SimConfig(ConfigFile.from_string(text))
    wide = SimConfig(ConfigFile.from_string(
        text + "[dram_directory]\ntotal_entries = 16384\n"
        "associativity = 128\n"))
    staged = dict(dir_stage=True, inner_block=4)
    programs = {
        "solo_staged": lambda: Simulator(
            narrow, trace(7), mem_gate_bytes=0, **staged).lower(4096)[0],
        "solo_staged_both_kernels": lambda: Simulator(
            wide, trace(7), mem_gate_bytes=0, **staged).lower(4096)[0],
        "served_unstaged": lambda: spec_from_sweep("", SweepRunner(
            narrow, [trace(7), trace(8)])).closed,
        "served_staged": lambda: spec_from_sweep("", SweepRunner(
            wide, [trace(7), trace(8)], **staged)).closed,
    }
    os.makedirs(OUT, exist_ok=True)
    for name, make in programs.items():
        closed = make()
        mlir = jax.jit(core.jaxpr_as_fun(closed)).trace(
            *closed.in_avals).lower(lowering_platforms=("tpu",)).as_text()
        with open(os.path.join(
                OUT, f"fold52_{os.path.basename(ROOT)}.{name}.mlir"),
                "w") as f:
            f.write(mlir)
        print(f"{name}: {len(mlir)} characters, "
              f"{mlir.count('@tpu_custom_call(')} kernels, "
              f"{mlir.count(chr(34) + 'stablehlo.scatter' + chr(34))} "
              f"scatters")


def compare(a, b):
    """The texts `--lowered` wrote for two checkouts, value names
    stripped: which lines differ, and what of a kernel's payload does."""
    import base64
    import collections
    import re

    def lines(text):
        return collections.Counter(
            re.sub(r"%[\w.\-#:]+", "%", ln).strip()
            for ln in text.splitlines())

    def payloads(text):
        return [re.findall(rb"[\x20-\x7e]{6,}", base64.b64decode(m))
                for m in re.findall(
                    r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)]

    for name in ("solo_staged", "solo_staged_both_kernels",
                 "served_unstaged", "served_staged"):
        ta, tb = (open(os.path.join(OUT, f"fold52_{x}.{name}.mlir")).read()
                  for x in (a, b))
        if ta == tb:
            print(f"{name}: identical, {len(ta)} characters")
            continue
        la, lb = lines(ta), lines(tb)
        # (`% = stablehlo.while(...` -> `stablehlo.while`; a line that
        # defines nothing by its first word)
        kinds = collections.Counter(
            (ln.split()[2] if ln.startswith("% =") else ln.split()[0])
            .split("(")[0] for ln in list(la - lb) + list(lb - la))
        print(f"{name}: {sum((la - lb).values())} lines of {a} and "
              f"{sum((lb - la).values())} of {b} differ with names "
              f"stripped: {dict(kinds)}")
        for pa, pb in zip(payloads(ta), payloads(tb)):
            print(f"    a kernel's payload: {len(pa)} / {len(pb)} strings, "
                  f"differing: {[(x, y) for x, y in zip(pa, pb) if x != y]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--lowered", metavar="CHECKOUT")
    ap.add_argument("--compare", nargs=2, metavar="NAME")
    ap.add_argument("--seed", type=int, default=52)
    args = ap.parse_args()
    if args.lowered:
        return lowered(args.lowered)
    if args.compare:
        return compare(*args.compare)
    if args.rehearse:
        B, T, DS, DW, SW, C = 2, 8, 128, 8, 16, 12
        lives, fills, trips, repeats = (0, 20), ((0, 1), (12, 6)), (1, 2), 1
    else:
        B, T, DS, DW, SW, C = 4, 256, 1024, 16, 8, 96
        lives, fills = (0, 300, 3072), ((0, 1), (600, 6), (1024, 32))
        trips, repeats = (4, 20), 3
    e_shape, s_shape = (B, T, 2 * DW, DS), (B, T, DS, DW * SW)
    cases = []
    for n_live in lives:
        cases.append((f"entry, {n_live} live words", e_shape,
                      lambda form: looped_entry(form, (B, T, DS, DW)),
                      entry_forms(args.rehearse),
                      entry_plan((B, T, DS, DW), n_live, args.seed)))
    for n_lanes, depth in fills:
        tab = stage_table((B, T, DS, DW, SW, C), n_lanes, depth, args.seed)
        cases.append((f"flush, {int(tab[2].sum())} staged slots", s_shape,
                      lambda form: looped_stage(form, (B, T, DS, DW, SW, C)),
                      stage_forms(args.rehearse), tab))
    if args.describe:
        # (one case a landing: the cell's few hundred live words, and the
        # middle fill of the table)
        return describe(cases[1:2] + cases[-2:-1])

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; entry words "
          f"u32{list(e_shape)}, sharers u32{list(s_shape)}, table "
          f"[{B}, {T}, {C}] x {SW} words; trips {trips}, best of {repeats}")
    rows = []
    for what, store_shape, looped, forms, operands in cases:
        want = None
        for name, form in forms:
            try:
                row = time_form(name, looped(form), store_shape, operands,
                                trips, repeats)
            except Exception as e:  # noqa: BLE001 — a form the chip refuses
                print(f"{what} {name}: FAILED {str(e)[:600]}")
                continue
            if want is None:
                want = row["digest"]
            row["case"], row["equals_a"] = what, row["digest"] == want
            rows.append(row)
            print(f"{what:28s} {name:34s} {row['landing_ms']:9.4f} ms a "
                  f"landing equal={row['equals_a']} (first call "
                  f"{row['first_call_s']:.1f} s)", flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "fold52.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "trips": trips, "rows": rows}, f, indent=1)
    return 1 if any(not r["equals_a"] for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
