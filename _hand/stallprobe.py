"""By-hand probe (PR 42): where does a stalled reading of a solo cell sit?

    chiprun --chips 1 --timeout 1500 -- python3 _hand/stallprobe.py \
        <cell> <seconds> [<out file>]

Sets the cell up as benchmark/run.py does, then makes readings back to
back for <seconds> with every runner call and `jax.device_get` timed,
`gc.callbacks` on every full collection, and a side thread that sleeps
2 ms at a time: a gap in its heartbeat while the main thread sits in a
fetch (GIL released) means the whole process was stopped.  For every
reading slower than 1.04 x the running median it prints the calls, the
gaps and the collections inside it; at 1.6 x it dumps the stacks and who
used CPU.  TIGHT_S=<s> runs a tight loop of tiny dispatch + fetch round
trips first (is a stall a cost per round trip?), FREEZE_AFTER_S=<s> calls
gc.collect(); gc.freeze() that far into the window (is it the collector?).
What it found is in PERF.md, section 6, PR 42, 'After the refusal'.  Runs on the CPU too
(JAX_PLATFORMS=cpu), where it only rehearses.
"""
import faulthandler
import glob
import os
import statistics as st
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

cell_name = sys.argv[1]
seconds = float(sys.argv[2])
out = open(sys.argv[3], "w") if len(sys.argv) > 3 else sys.stdout


def say(*a):
    print(*a, file=out)
    out.flush()


def read(path):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def cgroup_files():
    rel = ""
    for line in read("/proc/self/cgroup").splitlines():
        parts = line.split(":", 2)
        if len(parts) == 3 and parts[0] == "0":
            rel = parts[2]
    base = "/sys/fs/cgroup" + rel
    return base


CG = cgroup_files()
say("nproc", os.cpu_count(), "affinity", len(os.sched_getaffinity(0)),
    "loadavg", read("/proc/loadavg").strip())
say("cgroup", CG, "cpu.max", read(CG + "/cpu.max").strip(),
    "| root cpu.max", read("/sys/fs/cgroup/cpu.max").strip())
say("cpu.stat", read(CG + "/cpu.stat").replace("\n", " "))
say("thp", read("/sys/kernel/mm/transparent_hugepage/enabled").strip(),
    "defrag", read("/sys/kernel/mm/transparent_hugepage/defrag").strip())
say("pressure cpu", read("/proc/pressure/cpu").replace("\n", " | "))
say("env", {k: v for k, v in os.environ.items()
            if k.startswith(("JAX", "XLA", "TPU", "LIBTPU", "TF_", "OMP",
                             "MALLOC", "LD_PRELOAD", "PYTHON"))})

from lib import paths, target  # noqa: E402
from lib.ctx import Ctx  # noqa: E402

manifest = paths.load_manifest()
cell = [w for w in manifest["workloads"] if w["name"] == cell_name][0]
config = target.load_config(cell["config"])
traffic = paths.load_json("traffic", cell["traffic"] + ".json")
reference = target.load_reference(cell["config"])
driver = paths.load_module("drivers", traffic["driver"])

import graphite_tpu  # noqa: E402,F401
import jax  # noqa: E402

say("devices", jax.devices())
ctx = Ctx(cell=cell, config=config, traffic=traffic, reference=reference,
          seed=1, seconds=seconds)
t = time.perf_counter()
driver.setup(ctx)
say(f"set-up {time.perf_counter() - t:.1f} s")
sim = ctx.own["sim"]

# --- timed wrappers -------------------------------------------------------
events = []            # (name, t0, t1)
_dg = jax.device_get
_bur = jax.block_until_ready


def device_get(x):
    t0 = time.perf_counter()
    r = _dg(x)
    events.append(("device_get", t0, time.perf_counter()))
    return r


jax.device_get = device_get
if sim.barrier_host:
    _runner = sim._hb_get_runner()

    def runner(*a):
        t0 = time.perf_counter()
        r = _runner(*a)
        events.append(("dispatch", t0, time.perf_counter()))
        return r

    sim._hb_get_runner = lambda: runner

# --- gc ---------------------------------------------------------------------
import gc  # noqa: E402
gcs = []               # (t_start, seconds, generation)


def _gc_cb(phase, info):
    if phase == "start":
        _gc_cb.t = time.perf_counter()
    elif info["generation"] == 2:
        gcs.append((_gc_cb.t, time.perf_counter() - _gc_cb.t))


gc.callbacks.append(_gc_cb)

# --- the sampler ----------------------------------------------------------
samples = []           # (t, dict)
gaps = []              # (t, gap) heartbeat gaps over 20 ms
in_flight = [None]     # t0 of the reading in flight
dumped = [None]
stop = [False]


def proc_stat():
    f = read("/proc/stat").split("\n", 1)[0].split()[1:]
    f = [int(x) for x in f]
    names = ["user", "nice", "system", "idle", "iowait", "irq", "softirq",
             "steal"]
    return dict(zip(names, f))


def self_stat():
    f = read("/proc/self/stat").rsplit(")", 1)[1].split()
    return {"utime": int(f[11]), "stime": int(f[12]),
            "minflt": int(f[7]), "majflt": int(f[9])}


def cg_stat():
    d = {}
    for line in read(CG + "/cpu.stat").splitlines():
        k, v = line.split()
        d[k] = int(v)
    return d


def threads_cpu():
    d = {}
    for p in glob.glob("/proc/self/task/*/stat"):
        s = read(p)
        if not s:
            continue
        comm = s[s.index("(") + 1:s.rindex(")")]
        f = s.rsplit(")", 1)[1].split()
        d[p.split("/")[4] + ":" + comm] = (int(f[11]) + int(f[12]), f[0])
    return d


def procs_cpu():
    d = {}
    for p in glob.glob("/proc/[0-9]*/stat"):
        s = read(p)
        if not s:
            continue
        comm = s[s.index("(") + 1:s.rindex(")")]
        f = s.rsplit(")", 1)[1].split()
        d[p.split("/")[2] + ":" + comm] = int(f[11]) + int(f[12])
    return d


def sampler():
    last = time.perf_counter()
    next_sample = last
    while not stop[0]:
        time.sleep(0.002)
        now = time.perf_counter()
        if now - last > 0.02:
            gaps.append((now, now - last))
        last = now
        if now >= next_sample:
            next_sample = now + 0.05
            s = {"stat": proc_stat(), "self": self_stat(), "cg": cg_stat(),
                 "psi": read("/proc/pressure/cpu").split("\n")[0],
                 "load": read("/proc/loadavg").split()[0]}
            samples.append((now, s))
            if len(samples) > 4000:
                del samples[:2000]
        t0 = in_flight[0]
        if t0 is not None and now - t0 > 1.6 * typical[0] \
                and dumped[0] != t0:
            dumped[0] = t0
            say(f"--- a reading is {now - t0:.2f} s old: stacks, then who "
                f"runs for 0.4 s")
            faulthandler.dump_traceback(file=out, all_threads=True)
            a, pa = threads_cpu(), procs_cpu()
            time.sleep(0.4)
            b, pb = threads_cpu(), procs_cpu()
            say("my threads (ticks in 0.4 s, state): " + ", ".join(
                f"{k} {b[k][0] - a.get(k, (0,))[0]} {b[k][1]}"
                for k in b if b[k][0] - a.get(k, (0,))[0] > 0
                or b[k][1] not in "S"))
            say("processes (ticks in 0.4 s): " + ", ".join(
                f"{k} {pb[k] - pa.get(k, 0)}" for k in pb
                if pb[k] - pa.get(k, 0) > 0))
            say("all processes: " + " ".join(sorted(pb)))
            last = time.perf_counter()


typical = [1e9]
th = threading.Thread(target=sampler, daemon=True)
th.start()


def diff(a, b):
    return {k: b[k] - a[k] for k in a if isinstance(a[k], int)
            and b[k] != a[k]}


def report(i, t0, t1, wall):
    say(f"=== reading {i} at {t0 - W0:.1f} s into the window took "
        f"{wall:.4f} s (median {typical[0]:.4f})")
    ev = [e for e in events if e[1] >= t0 and e[2] <= t1 + 1e-3]
    say("  calls: " + " ".join(f"{n}:{1e3 * (b - a):.1f}ms@{a - t0:.3f}"
                               for n, a, b in ev))
    g = [(round(t - t0, 3), round(d, 3)) for t, d in gaps if t0 <= t <= t1]
    say(f"  sampler heartbeat gaps over 20 ms: {g}")
    say("  full collections (s into the reading, s): "
        f"{[(round(a - t0, 3), round(d, 3)) for a, d in gcs if t0 <= a <= t1]}")
    ss = [s for s in samples if t0 - 0.06 <= s[0] <= t1 + 0.06]
    if len(ss) >= 2:
        a, b = ss[0][1], ss[-1][1]
        say(f"  over {ss[-1][0] - ss[0][0]:.2f} s: /proc/stat ticks "
            f"{diff(a['stat'], b['stat'])}; self {diff(a['self'], b['self'])}"
            f"; cgroup {diff(a['cg'], b['cg'])}")
        say(f"  psi {a['psi']} -> {b['psi']}; load {a['load']} -> "
            f"{b['load']}")


# --- phase 1: a tight loop of tiny dispatches and fetches -------------------
import jax.numpy as jnp  # noqa: E402

tight_s = float(os.environ.get("TIGHT_S", "0"))
if tight_s:
    @jax.jit
    def tiny(x, b):
        y = x + b.astype(x.dtype)
        return y, y.sum(), y.max(), y.min(), (y > 3).any(), y[0]

    x = jnp.zeros((256,), jnp.int64)
    jax.block_until_ready(tiny(x, jnp.asarray(1, jnp.int32)))
    T0 = time.perf_counter()
    durs = []
    slow = []
    while time.perf_counter() - T0 < tight_s:
        a = time.perf_counter()
        x, s1, s2, s3, s4, s5 = tiny(x, jnp.asarray(1, jnp.int32))
        _dg((s1, s2, s3, s4, s5, x))
        b = time.perf_counter()
        durs.append(b - a)
        if b - a > 0.03:
            slow.append((a, b))
    d = sorted(durs)
    say(f"tight loop: {len(d)} dispatch+fetch in {tight_s:g} s: median "
        f"{1e3 * st.median(d):.3f} ms p99 {1e3 * d[int(.99 * len(d))]:.3f} "
        f"max {1e3 * d[-1]:.1f} ms; over 30 ms: {len(slow)}")
    for a, b in slow[:40]:
        g = [round(x_, 3) for t_, x_ in gaps if a <= t_ <= b + 0.01]
        c = [round(x_, 3) for t_, x_ in gcs if a - 0.001 <= t_ <= b]
        say(f"  at {a - T0:.1f} s: {1e3 * (b - a):.1f} ms; heartbeat gaps "
            f"{g}; full collections {c}")
    say(f"  full collections in the tight loop: {len([1 for t_, _ in gcs if t_ >= T0])}")
    del gaps[:]

freeze_after = float(os.environ.get("FREEZE_AFTER_S", "0"))
frozen = False
W0 = time.perf_counter()
walls = []
i = 0
while time.perf_counter() - W0 < seconds:
    if freeze_after and not frozen \
            and time.perf_counter() - W0 > freeze_after:
        frozen = True
        gc.collect()
        gc.freeze()
        say(f"--- gc.collect(); gc.freeze() at {time.perf_counter() - W0:.1f}"
            f" s into the window, after reading {i - 1}: {gc.get_freeze_count()}"
            " objects")
    t0 = time.perf_counter()
    in_flight[0] = t0
    n0 = len(ctx.readings)
    driver._one_reading(ctx)
    t1 = time.perf_counter()
    in_flight[0] = None
    wall = t1 - t0
    walls.append(wall)
    if len(walls) >= 3:
        typical[0] = st.median(walls)
        if wall > 1.04 * typical[0]:
            report(i, t0, t1, wall)
    # keep the host's memory flat: the judge is not what is probed here
    del ctx.readings[1:]
    del events[:]
    i += 1
stop[0] = True
w = sorted(walls)
say(f"{len(w)} readings in {time.perf_counter() - W0:.1f} s: min {w[0]:.4f} "
    f"median {st.median(w):.4f} p99 {w[int(0.99 * (len(w) - 1))]:.4f} "
    f"max {w[-1]:.4f}; over 1.04 x median: "
    f"{[round(x, 3) for x in w if x > 1.04 * st.median(w)]}")
say("full collections (s into the window, s):",
    [(round(a - W0, 1), round(d, 3)) for a, d in gcs if a >= W0])
say("cpu.stat", read(CG + "/cpu.stat").replace("\n", " "))
say("all heartbeat gaps over 20 ms:", len(gaps),
    [(round(t - W0, 1), round(d, 3)) for t, d in gaps][:60])
