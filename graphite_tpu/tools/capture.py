"""Capture REAL program executions as traces; calibrate the skeletons.

The reusable harness behind `capture_fft.py` (the one-off FFT capture,
generalized), plus real SPLASH-2-shaped
implementations of RADIX and LU recorded the same way.  These are not
synthetic generators: each app EXECUTES its algorithm — real data, true
addresses — under the live-recording Carbon API (the reference analog is
capturing a real binary under Pin, `pin/instruction_modeling.cc`).
Every arithmetic op is recorded as an instruction record and every
element access goes through `carbon_load`/`carbon_store`, so a replay
drives the full cache/coherence stack with the program's actual sharing
pattern, and `measured_mix` reports the real instruction mix — the
calibration source for the `trace/benchmarks.py` skeletons.

Validation (both apps, like the FFT capture):
 - functionally on replay: barrier-separated single-writer reads carry
   FLAG_CHECK — the coherence engine must reproduce every loaded value
   (func_errors == 0);
 - numerically at capture: radix output must equal numpy's sort; the
   LU factors must reconstruct the input matrix within fixed-point
   tolerance.

Usage:  python -m graphite_tpu.tools.capture {radix|lu} [out.npz]
"""

from __future__ import annotations

import numpy as np

FX = 16  # 16.16 fixed point (LU)


def _w32(v: int) -> int:
    return ((v & 0xFFFFFFFF) ^ 0x80000000) - 0x80000000


# --------------------------------------------------------------------------
# shared harness


def measured_mix(batch) -> dict:
    """Instruction/memory mix of a captured trace, by record type."""
    from graphite_tpu.trace.schema import (
        FLAG_MEM0_VALID, FLAG_MEM0_WRITE, Op,
    )

    op = batch.op
    flags = batch.flags
    mem = (flags & FLAG_MEM0_VALID) != 0
    return {
        "records": int((op != int(Op.NOP)).sum()),
        "fmul": int((op == int(Op.FMUL)).sum()),
        "falu": int((op == int(Op.FALU)).sum()),
        "fdiv": int((op == int(Op.FDIV)).sum()),
        "ialu": int((op == int(Op.IALU)).sum()),
        "loads": int((mem & ((flags & FLAG_MEM0_WRITE) == 0)).sum()),
        "stores": int((mem & ((flags & FLAG_MEM0_WRITE) != 0)).sum()),
    }


def replay_report(batch, n_tiles: int, out_path: str | None = None) -> dict:
    """Save (optionally), reload, and replay a captured batch through the
    full memory engine; report counters + the measured mix.  FLAG_CHECK
    loads make the replay a functional test of the coherence stack."""
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.tools._template import config_text
    from graphite_tpu.trace.io import load_trace_npz, save_trace_npz

    if out_path:
        save_trace_npz(out_path, batch)
        batch = load_trace_npz(out_path)
    sc = SimConfig(ConfigFile.from_string(config_text(
        n_tiles, shared_mem=True, clock_scheme="lax")))
    res = Simulator(sc, batch).run()
    return {
        "npz": out_path,
        "func_errors": res.func_errors,
        "completion_ns": res.completion_time_ps // 1000,
        "instructions": res.total_instructions,
        "l2_misses": int(np.asarray(res.mem_counters["l2_misses"]).sum()),
        "mix": measured_mix(batch),
    }


def make_app(n_tiles: int):
    """A CarbonApp over the standard capture config."""
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.frontend import carbon_api as capi
    from graphite_tpu.tools._template import config_text

    sc = SimConfig(ConfigFile.from_string(config_text(
        n_tiles, shared_mem=True, clock_scheme="lax")))
    return capi.CarbonApp(sc)


def run_threads(app, worker, n_tiles: int, *args):
    """main_fn boilerplate: spawn `worker(tile, barrier, *args)` on every
    tile, join.  Returns the recorded TraceBatch."""
    from graphite_tpu.frontend import carbon_api as capi

    def main_fn():
        bar = capi.CarbonBarrier(n_tiles)
        tids = [capi.carbon_spawn_thread(worker, t, bar, *args)
                for t in range(1, n_tiles)]
        worker(0, bar, *args)
        for tid in tids:
            capi.carbon_join_thread(tid)

    return app.start(main_fn)


# --------------------------------------------------------------------------
# RADIX: real parallel LSD radix sort (SPLASH-2 `kernels/radix/radix.C`:
# per digit pass — local histogram, global rank bases, permutation).


def run_radix_app(n_tiles: int = 4, keys_per_tile: int = 256,
                  radix: int = 16, n_digits: int = 2, seed: int = 17):
    """Execute a parallel radix sort under the recording API.

    Returns (TraceBatch, input_keys, output_keys).  Keys are drawn
    < radix**n_digits so n_digits passes sort completely; the sort is
    the textbook stable counting-sort-per-digit of the SPLASH-2 kernel
    (local histogram -> cross-tile rank bases -> permutation), with the
    rank arrays and the key buffers truly shared (rank reads and the
    permutation's scattered writes cross tile-partition boundaries)."""
    from graphite_tpu.frontend import carbon_api as capi

    T = n_tiles
    N = T * keys_per_tile
    bits = radix.bit_length() - 1
    assert 1 << bits == radix
    # region layout bounds (aliasing window documented below): keys must
    # fit the 64 KB per-array slots, histograms/ranks their 32/16 KB
    assert 4 * N <= 0x10000, "key arrays overrun the region layout"
    # RANK has the narrowest slot (16 KB, 0x128000..0x12C000)
    assert 4 * T * radix <= 0x4000, "hist/rank overrun the region layout"
    # all regions inside one 256 KB window: the replay's functional
    # memory maps addr>>2 modulo general/functional_memory_kb*256 words
    # (memory/params.py:440), so wider spacing would alias
    A, B = 0x100000, 0x110000          # double-buffered key arrays
    HIST = 0x120000                    # hist[t][d] per-tile histograms
    RANK = 0x128000                    # rank[t][d] global write bases
    TOT = 0x12C000                     # digit totals + prefix

    rng = np.random.default_rng(seed)
    keys = rng.integers(0, radix ** n_digits, size=N).astype(np.int64)

    def worker(tile, bar):
        lo, hi = tile * keys_per_tile, (tile + 1) * keys_per_tile
        # setup: each tile stores its own slice of the input
        for i in range(lo, hi):
            capi.carbon_store(A + 4 * i, int(keys[i]))
        bar.wait()
        for p in range(n_digits):
            src = A if p % 2 == 0 else B
            dst = B if p % 2 == 0 else A
            shift = p * bits
            # ---- phase 1: local histogram (private accumulation, one
            # shared store per digit — radix.C keeps density private)
            hist = [0] * radix
            for i in range(lo, hi):
                k = capi.carbon_load(src + 4 * i, check=True)
                capi.carbon_instr()          # digit extract (shift+mask)
                hist[(k >> shift) & (radix - 1)] += 1
            for d in range(radix):
                capi.carbon_instr()          # store index arithmetic
                capi.carbon_store(HIST + 4 * (tile * radix + d), hist[d])
            bar.wait()
            # ---- phase 2: rank bases.  Digits distributed round-robin:
            # each owner sums its digits across ALL tiles' histograms
            # (true read-sharing) and writes per-(tile, digit) bases.
            for d in range(tile, radix, T):
                run = 0
                for t2 in range(T):
                    capi.carbon_instr()      # index arithmetic
                    h = capi.carbon_load(
                        HIST + 4 * (t2 * radix + d), check=True)
                    capi.carbon_store(RANK + 4 * (t2 * radix + d), run)
                    run += h
                capi.carbon_store(TOT + 4 * d, run)
            bar.wait()
            # digit-total exclusive prefix (tile 0 — small serial tail;
            # radix.C uses a prefix tree, same O(radix) work overall)
            if tile == 0:
                run = 0
                for d in range(radix):
                    tot = capi.carbon_load(TOT + 4 * d, check=True)
                    capi.carbon_instr()      # accumulate
                    capi.carbon_store(TOT + 4 * (radix + d), run)
                    run += tot
            bar.wait()
            # ---- phase 3: permutation — stable scatter of own keys to
            # their globally ranked positions (all-to-all true writes)
            base = {}
            for i in range(lo, hi):
                k = capi.carbon_load(src + 4 * i, check=True)
                capi.carbon_instr()          # digit extract
                d = (k >> shift) & (radix - 1)
                if d not in base:
                    pre = capi.carbon_load(TOT + 4 * (radix + d),
                                           check=True)
                    rb = capi.carbon_load(RANK + 4 * (tile * radix + d),
                                          check=True)
                    base[d] = pre + rb
                capi.carbon_instr()          # dest address arithmetic
                capi.carbon_store(dst + 4 * base[d], k)
                base[d] += 1
            bar.wait()

    app = make_app(T)
    batch = run_threads(app, worker, T)
    out_base = B if n_digits % 2 == 1 else A
    out = np.array([_w32(app._memory.get(out_base + 4 * i, 0))
                    for i in range(N)], np.int64)
    return batch, keys, out


# --------------------------------------------------------------------------
# LU: real blocked dense LU factorization, no pivoting (SPLASH-2
# `kernels/lu/lu.C`: per step — diagonal factor, perimeter solves,
# interior update; block-cyclic ownership) in 16.16 fixed point.


def run_lu_app(n_tiles: int = 4, n: int = 32, block: int = 8,
               seed: int = 23):
    """Execute a blocked LU factorization under the recording API.

    Returns (TraceBatch, input_matrix_float, lu_in_place_float).
    Diagonally dominant integer input keeps the no-pivoting
    factorization exact-friendly in fixed point."""
    from graphite_tpu.frontend import carbon_api as capi

    T = n_tiles
    NB = n // block
    assert NB * block == n
    ABASE = 0x400000
    # single region: must fit the 256 KB functional-memory window
    assert 4 * n * n <= 0x40000, "matrix overruns the functional window"

    def addr(i, j):
        return ABASE + 4 * (i * n + j)

    rng = np.random.default_rng(seed)
    a0 = rng.integers(-8, 9, size=(n, n)).astype(np.int64)
    np.fill_diagonal(a0, a0.diagonal() + 16 * n)   # dominance: |L| < 1
    afx = a0 << FX

    # 2-D block-cyclic ownership over a ~sqrt(T) grid (lu.C's
    # proc-grid scatter) — keeps rows AND columns spread across tiles
    pr = max(1, int(np.sqrt(T)))
    pc = max(1, T // pr)

    def owner(bi, bj):
        return (bi % pr) * pc + (bj % pc)

    def load_block(bi, bj, check):
        """Load a block's elements (true addresses) into a local dict."""
        blk = {}
        r0, c0 = bi * block, bj * block
        for r in range(block):
            for c in range(block):
                blk[(r, c)] = _w32(capi.carbon_load(
                    addr(r0 + r, c0 + c), check=check))
        return blk

    def store_block(bi, bj, blk):
        r0, c0 = bi * block, bj * block
        for r in range(block):
            for c in range(block):
                capi.carbon_store(addr(r0 + r, c0 + c),
                                  _w32(blk[(r, c)]))

    def fxmul(a, b):
        capi.carbon_instr(capi.Op.FMUL)
        return (a * b) >> FX

    def fxdiv_recip(d):
        capi.carbon_instr(capi.Op.FDIV)
        return ((1 << (2 * FX)) + (d // 2)) // d if d else 0

    def worker(tile, bar):
        # setup: block owners store their blocks of the input
        for bi in range(NB):
            for bj in range(NB):
                if owner(bi, bj) == tile:
                    blk = {(r, c): int(afx[bi * block + r, bj * block + c])
                           for r in range(block) for c in range(block)}
                    store_block(bi, bj, blk)
        bar.wait()
        for k in range(NB):
            # ---- diagonal factor (lu.C lu0): in-place LU of block (k,k)
            if owner(k, k) == tile:
                dk = load_block(k, k, check=True)
                for j in range(block):
                    recip = fxdiv_recip(dk[(j, j)])
                    for i in range(j + 1, block):
                        dk[(i, j)] = fxmul(dk[(i, j)], recip)
                        for m in range(j + 1, block):
                            capi.carbon_instr(capi.Op.FALU)
                            dk[(i, m)] -= fxmul(dk[(i, j)], dk[(j, m)])
                store_block(k, k, dk)
            bar.wait()
            # ---- perimeter (lu.C bdiv/bmodd): row blocks (k, j) get
            # L(k,k)^-1 applied; column blocks (i, k) get U(k,k)^-1.
            # Every perimeter owner RE-LOADS the diagonal block — the
            # read-sharing the shared-memory original exhibits.
            prow = [j for j in range(k + 1, NB) if owner(k, j) == tile]
            pcol = [i for i in range(k + 1, NB) if owner(i, k) == tile]
            if prow or pcol:
                dk = load_block(k, k, check=True)
            for j in prow:
                blk = load_block(k, j, check=True)
                for c in range(block):
                    for r in range(block):
                        for q in range(r):
                            capi.carbon_instr(capi.Op.FALU)
                            blk[(r, c)] -= fxmul(dk[(r, q)], blk[(q, c)])
                store_block(k, j, blk)
            for i in pcol:
                blk = load_block(i, k, check=True)
                recips = [fxdiv_recip(dk[(q, q)]) for q in range(block)]
                for r in range(block):
                    for c in range(block):
                        for q in range(c):
                            capi.carbon_instr(capi.Op.FALU)
                            blk[(r, c)] -= fxmul(blk[(r, q)], dk[(q, c)])
                        blk[(r, c)] = fxmul(blk[(r, c)], recips[c])
                store_block(i, k, blk)
            bar.wait()
            # ---- interior (lu.C bmod): A(i,j) -= A(i,k) @ A(k,j)
            mine = [(i, j) for i in range(k + 1, NB)
                    for j in range(k + 1, NB) if owner(i, j) == tile]
            for (i, j) in mine:
                li = load_block(i, k, check=True)
                uj = load_block(k, j, check=True)
                blk = load_block(i, j, check=True)
                for r in range(block):
                    for c in range(block):
                        for q in range(block):
                            capi.carbon_instr(capi.Op.FALU)
                            blk[(r, c)] -= fxmul(li[(r, q)], uj[(q, c)])
                store_block(i, j, blk)
            bar.wait()

    app = make_app(T)
    batch = run_threads(app, worker, T)
    lu = np.empty((n, n), np.float64)
    for i in range(n):
        for j in range(n):
            lu[i, j] = _w32(app._memory.get(addr(i, j), 0)) / (1 << FX)
    return batch, a0.astype(np.float64), lu


def verify_lu(a0: np.ndarray, lu: np.ndarray) -> float:
    """Max relative reconstruction error |L@U - A| / |A|."""
    n = a0.shape[0]
    L = np.tril(lu, -1) + np.eye(n)
    U = np.triu(lu)
    scale = max(1.0, float(np.abs(a0).max()))
    return float(np.abs(L @ U - a0).max() / scale)


# --------------------------------------------------------------------------
# CLI


def main(which: str, out_path: str | None = None) -> dict:
    if which == "radix":
        batch, keys, out = run_radix_app()
        sorted_ok = bool((np.sort(keys) == out).all())
        report = replay_report(batch, 4, out_path)
        n_keys = len(keys)
        report.update(
            sorted_ok=sorted_ok,
            records_per_key_per_pass=report["mix"]["records"] / n_keys / 2,
            loads_per_key_per_pass=report["mix"]["loads"] / n_keys / 2,
        )
        assert sorted_ok, "captured radix sort produced a wrong order"
    elif which == "lu":
        batch, a0, lu = run_lu_app()
        err = verify_lu(a0, lu)
        report = replay_report(batch, 4, out_path)
        b3 = 8 ** 3
        report.update(numeric_max_rel_err=err,
                      fp_per_b3=(report["mix"]["fmul"]
                                 + report["mix"]["falu"]
                                 + report["mix"]["fdiv"]) / b3)
        assert err < 5e-2, f"captured LU reconstruction error {err}"
    else:
        raise SystemExit(f"unknown app {which!r} (radix|lu)")
    assert report["func_errors"] == 0, "replay FLAG_CHECK mismatches"
    return report


if __name__ == "__main__":
    import json
    import sys

    which = sys.argv[1] if len(sys.argv) > 1 else "radix"
    out = sys.argv[2] if len(sys.argv) > 2 else None
    print(json.dumps(main(which, out), indent=1))
