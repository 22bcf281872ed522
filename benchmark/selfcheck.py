"""The benchmark checks itself.  CPU, tiny sizes, seconds; prints no
contract line.

    JAX_PLATFORMS=cpu python benchmark/selfcheck.py

- the trace reducer against a hand-built trace and the small recorded
  one (busy union, idle share, top operations, launch count, gap labels);
- the digest against hand-built statistics;
- the loaders against every file under configs/, references/, traffic/,
  layer_metrics/, drivers/ and every entry of BENCHMARK.json;
- run.py end to end on the self-check's 16-tile stand-in, with the look
  for a chip stubbed here and only here; then the same run
    * without the stub: refused, no result;
    * as the control (one guarantee of the configuration broken):
      `correct` false;
    * with the timed path broken underneath (a clock altered where it is
      produced; a reading that raises): `correct` false;
    * with a program compiling inside the window: refused, no result.
"""

import contextlib
import copy
import hashlib
import io
import json
import os
import re
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from lib import digest, paths, target, xplane  # noqa: E402

NAME = target.NAME
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
CELL = "selfcheck-16"


def check(ok, what):
    if not ok:
        raise SystemExit(f"selfcheck: FAILED: {what}")


# -- the trace reducer ------------------------------------------------------

def hand_trace():
    """One device, a window of 1000 ns (bench:run 100..1100).  Program A
    200..600 holds a while 210..590 that encloses f1 220..320 and f2
    400..500; program B 800..900 holds f1 810..860.  Busy = 100 + 100 +
    50 = 250.  Gaps: 100..220 (midpoint 160: host in run), 320..400 (in
    A), 500..810 (655: host in run... the innermost span is `fetch`
    640..700), 860..1100 (980: host in run)."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_A(1)", 200, 400], ["jit_B(2)", 800, 100]]},
            {"name": "XLA Ops", "events": [
                ["while.1", 210, 380], ["f1", 220, 100], ["f2", 400, 100],
                ["f1", 810, 50]]},
            {"name": "Steps", "events": [["0", 0, 5000]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench:run", 100, 1000], ["bench:fetch", 640, 60],
                ["other", 0, 5000]]}]},
    ]}


def check_reducer():
    check(xplane.union_length([(0, 10), (5, 20), (30, 40)]) == 30,
          "union of overlapping intervals")
    r = xplane.reduce(hand_trace())
    check(abs(r["window_s"] - 1000e-9) < 1e-15, f"window {r['window_s']}")
    check(abs(r["busy_s"] - 250e-9) < 1e-15, f"busy {r['busy_s']}")
    check(abs(r["idle_share"] - 0.75) < 1e-12, f"idle {r['idle_share']}")
    check(abs(r["in_program_s"] - 500e-9) < 1e-15, "time inside programs")
    check(r["launches"] == 2, f"launches {r['launches']}")
    ops = dict(r["breakdown"]["device_ops"])
    check(set(ops) == {"f1", "f2"} and abs(ops["f1"] - 150e-9) < 1e-15,
          f"top operations {ops} (the enclosing while must not count)")
    gaps = dict(r["breakdown"]["idle_gaps"])
    want = {
        "no program on the device; host in bench:run": 120 + 240,
        "device, inside program jit_A(1) (between operations)": 80,
        "no program on the device; host in bench:fetch": 310,
    }
    check({k: round(v * 1e9) for k, v in gaps.items()} == want,
          f"idle gaps by label: {gaps}")
    check(abs(sum(gaps.values()) + r["busy_s"] - r["window_s"]) < 1e-15,
          "gaps + busy do not add up to the window")
    rec_path = os.path.join(HERE, "lib", "recorded_trace.json")
    with open(rec_path) as f:
        rec = json.load(f)
    r = xplane.reduce(rec["trace"])
    for k, v in rec["expected"].items():
        check(abs(r[k] - v) <= 1e-9 * max(1.0, abs(v)),
              f"recorded trace: {k} = {r[k]!r}, recorded {v!r}")
    check(r["busy_s"] <= r["window_s"], "busy over the window")
    return 2


# -- the digest -------------------------------------------------------------

def check_digest():
    import numpy as np

    from graphite_tpu.engine.simulator import SimResults

    z = np.zeros(2, np.int64)
    res = SimResults(
        n_tiles=2, completion_time_ps=7, instruction_count=np.array([3, 4]),
        clock_ps=np.array([5, 7]), memory_stall_ps=z, execution_stall_ps=z,
        recv_instructions=z, recv_stall_ps=z, sync_instructions=z,
        sync_stall_ps=z, bp_correct=z, bp_incorrect=z, packets_sent=z,
        packets_received=z, total_packet_latency_ps=z, n_quanta=1,
        mem_counters={"l2_misses": np.array([1, 2], np.int32)})
    stats = digest.statistics(res)
    check(int(stats["total_instructions"]) == 7
          and "mem_counters.l2_misses" in stats and "hist" not in stats,
          f"statistics {sorted(stats)}")
    hs = digest.hashes(stats)
    by_hand = hashlib.sha256(
        b"clock_ps|(2,)|" + np.array([5, 7], "<i8").tobytes()).hexdigest()
    check(hs["clock_ps"] == by_hand, "clock_ps hash is not the one by hand")
    check(hs["mem_counters.l2_misses"] == digest.sha_of(
        "mem_counters.l2_misses", np.array([1, 2], np.int64)),
        "the hash depends on the integer width, not on the values")
    ref = {k: {"sha256": v} for k, v in hs.items()}
    check(digest.compare(hs, ref) == [], "a run differs from itself")
    res.clock_ps = np.array([5, 8])
    moved = digest.compare(digest.hashes(digest.statistics(res)), ref)
    check(moved == ["clock_ps"], f"one moved picosecond shows as {moved}")
    check(digest.compare({}, ref) == sorted(ref), "a missing statistic")
    return 1


# -- the loaders and the manifest ------------------------------------------

def check_files():
    n = 0
    manifest = paths.load_manifest()
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    check(len(cells) == len(manifest["workloads"])
          and len(configs) == len(manifest["configs"]), "a name twice")
    for name in sorted(os.listdir(os.path.join(HERE, "configs"))):
        cfg = target.load_config(name[:-len(".json")])
        ref = target.load_reference(name[:-len(".json")])
        check(ref["digest"] == digest.combined(
            {k: v["sha256"] for k, v in ref["statistics"].items()}),
            f"references/{name}: digest does not match its statistics")
        check(ref["trace"] == cfg["trace"]
              and all(cfg["config_text"].get(k) == v
                      for k, v in ref["config_text"].items()),
              f"references/{name} was made from another configuration")
        check("control" in cfg and cfg["control"]["breaks"],
              f"configs/{name} names no control")
        n += 1
    for name in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        t = paths.load_json("traffic", name)
        check(hasattr(paths.load_module("drivers", t["driver"]), "window"),
              f"traffic/{name}: driver {t['driver']}")
        n += 1
    for c in manifest["configs"]:
        check(NAME.match(c["name"]) and LINE.match(c["source"])
              and LINE.match(c["why"]), f"config entry {c['name']}")
        cfg = target.load_config(c["name"])
        check(c["file"] == f"benchmark/configs/{c['name']}.json"
              and c["source"] == cfg["source"]
              and c["reduced"] == cfg["reduced"],
              f"config entry {c['name']} and its file disagree")
    for w in manifest["workloads"]:
        check(NAME.match(w["name"]) and NAME.match(w["traffic"])
              and w["config"] in configs and w["chips"] in (1, 4)
              and LINE.match(w["why"]), f"workload entry {w['name']}")
        paths.load_json("traffic", w["traffic"] + ".json")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        check(NAME.match(m["name"]) and UNIT.match(m["unit"])
              and m["better"] in ("lower", "higher")
              and set(m.get("workloads", cells)) <= set(cells),
              f"metric entry {m['name']}")
    check("setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25
                                   for m in e2e.values()), "bounds")
    for m in manifest["per_layer"]:
        check(hasattr(paths.load_module("layer_metrics", m["name"]), "read"),
              f"layer_metrics/{m['name']}.py has no read()")
        check(LINE.match(m["layer"]) and m["moves"] in e2e,
              f"per-layer {m['name']}: moves {m['moves']}")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            check(cell in moved.get("workloads", cells),
                  f"{m['name']} is read in {cell}, which does not report "
                  f"{m['moves']}")
        n += 1
    for cell in cells:
        check(any(cell in m.get("workloads", cells)
                  for m in manifest["per_layer"]), f"{cell}: no per-layer")
    return n


# -- run.py end to end at 16 tiles -----------------------------------------

def tiny_manifest():
    m = copy.deepcopy(REAL_MANIFEST)
    m["configs"].append({"name": "tiny-16"})
    m["workloads"].append({"name": CELL, "config": "tiny-16",
                           "traffic": "solo-repeat", "chips": 1})
    for x in m["end_to_end"] + m["per_layer"]:
        if "workloads" in x:
            x["workloads"].append(CELL)
    return m


def drive(main, trace=None, stub=True, seconds="1.5"):
    """(exit code, the contract line or None, all output); nothing of it
    reaches this process's stdout.  The look for a chip is stubbed here,
    by patching `run.check_device`, and nowhere else."""
    buf = io.StringIO()
    argv = ["--workload", CELL, "--seed", "2147483659", "--seconds", seconds]
    if trace is not None:
        argv += ["--trace", str(trace)]
    real_check = run.check_device
    if stub:
        run.check_device = lambda n, peaks: None
    try:
        with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
            rc = main(argv)
    finally:
        run.check_device = real_check
    lines = buf.getvalue().strip().splitlines()
    doc = None
    if main is run.main and lines and lines[-1].startswith('{"correct"'):
        doc = json.loads(lines[-1])
    return rc, doc, buf.getvalue()


def check_end_to_end():
    from graphite_tpu.engine import simulator
    from lib import profile

    rc, doc, out = drive(run.main, 0)
    check(rc == 0 and doc and doc["correct"] is True and doc["failed"] == 0
          and doc["attempted"] >= 2, f"a sound run: rc {rc}\n{out}")
    check(set(doc) == {"correct", "attempted", "failed", "metrics", "device"}
          and set(doc["metrics"]) == {"sim_records_per_s", "run_wall_p95_s",
                                      "peak_hbm_gb", "setup_s"},
          f"the line's keys: {doc}")
    check("(limit 0)" in out, "numbers are not printed beside their limits")

    # the traced run, its reduction fed from the recorded trace (a CPU
    # trace has no device plane)
    with open(os.path.join(HERE, "lib", "recorded_trace.json")) as f:
        rec = json.load(f)["trace"]
    real_reduce = profile.reduce_last
    profile.reduce_last = lambda: xplane.reduce(rec)
    try:
        rc, doc, out = drive(run.main, 1)
    finally:
        profile.reduce_last = real_reduce
    check(rc == 0 and doc["correct"] is True
          and {"busy_s", "window_s"} <= set(doc["device"])
          and 0 < doc["device"]["busy_s"] <= doc["device"]["window_s"]
          and set(doc["breakdown"]) == {"device_ops", "idle_gaps"}
          and {"records_per_iter", "wall_per_iter_ms", "compile_s",
               "outside_run_share", "device_idle_share"} <= set(doc["metrics"]),
          f"a traced run: rc {rc}, {doc}\n{out}")

    rc, doc, out = drive(run.main, 0, stub=False)
    check(rc != 0 and doc is None, "a CPU was taken for a chip")

    rc, doc, out = drive(control.main)
    check(rc == 0 and "differing from the reference" in out,
          f"the control came out correct\n{out}")

    real_run = simulator.Simulator.run

    def one_ps_late(self, *a, **k):
        res = real_run(self, *a, **k)
        res.clock_ps = res.clock_ps.copy()
        res.clock_ps[0] += 1
        return res

    def second_raises(self, *a, **k):
        second_raises.n += 1
        if second_raises.n == 2:
            raise simulator.DeadlockError("selfcheck")
        return real_run(self, *a, **k)

    def compiles(self, *a, **k):
        import jax

        compiles.n += 1
        jax.jit(lambda x: x + compiles.n)(1).block_until_ready()
        return real_run(self, *a, **k)

    second_raises.n = compiles.n = 0
    for broken, want in ((one_ps_late, "false"), (second_raises, "false"),
                         (compiles, "refused")):
        simulator.Simulator.run = broken
        try:
            rc, doc, out = drive(run.main, 0)
        finally:
            simulator.Simulator.run = real_run
        if want == "false":
            check(rc == 0 and doc["correct"] is False and doc["failed"] >= 1,
                  f"{broken.__name__}: correct came out true\n{out}")
        else:
            check(rc != 0 and doc is None,
                  f"{broken.__name__}: a compile in the window passed")
    return 7


if __name__ == "__main__":
    n = check_reducer() + check_digest() + check_files()
    REAL_MANIFEST = paths.load_manifest()
    real_load_json = paths.load_json

    def load_json_too(*parts):
        """The real files first, then the self-check's stand-in."""
        try:
            return real_load_json(*parts)
        except FileNotFoundError:
            with open(os.path.join(HERE, "selfcheck_data", *parts)) as f:
                return json.load(f)

    paths.load_json = load_json_too
    paths.load_manifest = tiny_manifest
    import control  # noqa: E402
    import run  # noqa: E402

    n += check_end_to_end()
    print(f"selfcheck: ok ({n} checks)")
