"""The served path as the benchmark's `campaign64-dram` cell drives it,
at 16 tiles on the CPU: served = solo bit for bit for the cell's traffic
(stores + shared lines, four DRAM latencies, a padded batch); the spans
and counters of a served grid; `SweepRunner.run()`'s drive-loop spans and
its program's hooks; and the cell's own self-check.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.obs import scopes
from graphite_tpu.obs.trace import (
    BATCH_SPANS, JOB_SPANS, RUN_SPANS, SETUP_SPANS, ProgramLedger, Span,
    Tracer,
)
from graphite_tpu.serve.job import Job
from graphite_tpu.serve.service import CampaignService
from graphite_tpu.sweep.runner import SweepRunner
from graphite_tpu.tools import report
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace.synthetic import memory_stress_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib.digest import statistics  # noqa: E402  (every statistic, by name)
TILES = 16
LATENCIES = (60, 100, 140, 180)
# a grid of 6 jobs: stream 0 at the four latencies, stream 1 at two: a
# full batch of 4 and a batch of 2 padded with two replicas
GRID = [(0, lat) for lat in LATENCIES] + [(1, 60), (1, 180)]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def text(latency_ns=None) -> str:
    t = config_text(TILES, core="iocoom", shared_mem=True)
    if latency_ns is not None:
        t += f"\n[dram]\nlatency = {latency_ns}\n"
    return t


def stream(seed: int):
    return memory_stress_trace(TILES, n_accesses=10, working_set_bytes=8192,
                               write_fraction=0.4, shared_fraction=0.5,
                               seed=seed)


class Clock:
    """One microsecond per reading: spans order strictly."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-6
        return self.t


@pytest.fixture(scope="module")
def served():
    """One grid of 6 jobs through a traced service; what every test of
    the served path below reads."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == COMPILE_EVENT else None)
    sc = SimConfig(ConfigFile.from_string(text()))
    traces = {s: stream(s) for s in (0, 1)}
    svc = CampaignService(batch_size=4, store=None, shard_batch=False,
                          n_devices=1, max_dwell_s=0,
                          tracing=Tracer(clock=Clock()))
    for s, lat in GRID:
        svc.submit(Job(job_id=f"s{s}-L{lat}", config=sc, trace=traces[s],
                       knobs={"dram_latency_ns": lat}, seed=s))
    envelopes = {e.job_id: e for e in svc.drain()}
    return {"svc": svc, "envelopes": envelopes, "traces": traces,
            "compiles": compiles}


@pytest.mark.parametrize("s,lat", GRID)
def test_served_equals_solo(served, s, lat):
    """A served job is bit-identical, on every statistic, to a plain
    `Simulator.run()` with its latency in the config text: nothing of
    serve/, sweep/, vmap or the knob operands in the reference."""
    env = served["envelopes"][f"s{s}-L{lat}"]
    assert env.status == "ok" and env.knob_point == {"dram_latency_ns": lat}
    sim = Simulator(SimConfig(ConfigFile.from_string(text(lat))),
                    served["traces"][s])
    assert sim.params.mem.dram_latency_ns == lat
    solo = statistics(sim.run())
    got = statistics(env.results)
    assert len(solo) == 46 and sorted(got) == sorted(solo)
    for k in solo:
        np.testing.assert_array_equal(got[k], solo[k], err_msg=k)
    # ... on its working iterations too; a batch's block runs while ANY
    # of its sims advanced, so a job also sat through its neighbours'
    assert env.n_iterations - env.idle_iterations \
        == sim.last_n_iterations - sim.last_idle_iterations
    assert env.n_iterations >= sim.last_n_iterations
    # the traffic does what the cell is for: stores and shared lines
    assert int(solo["mem_counters.l2_misses"].sum()) > 0
    assert int(np.asarray(env.results.func_errors)) == 0


def test_grid_counters(served):
    c = served["svc"].counters
    assert (c["submitted"], c["completed"], c["failed"]) == (6, 6, 0)
    assert (c["batches"], c["padded_slots"]) == (2, 2)
    assert (c["compile_count"], c["cache_hits"]) == (1, 1)
    log = list(served["svc"].batch_log)
    assert [(b.n_jobs, b.batch_cap) for b in log] == [(4, 4), (2, 4)]
    assert sorted(served["envelopes"]) == sorted(
        f"s{s}-L{lat}" for s, lat in GRID)


@pytest.mark.parametrize("batch", [0, 1])
def test_batch_spans_nested_and_ordered(served, batch):
    tracer = served["svc"].tracer
    spans = tracer.trace(f"batch-{batch}")
    assert all(not s.open for s in spans)
    by = {s.name: s for s in spans}
    assert set(by) - set(SETUP_SPANS) == \
        set(BATCH_SPANS) | set(RUN_SPANS) | {"batch"}
    # around the run: pack, build, cache, execute, demux, in that order,
    # none overlapping the next, all inside `batch`
    seq = [by[n] for n in BATCH_SPANS]
    for a, b in zip(seq, seq[1:]):
        assert a.t_end <= b.t_start, (a.name, b.name)
    assert by["batch"].t_start <= seq[0].t_start
    assert seq[-1].t_end <= by["batch"].t_end
    # inside `execute`: the runner's own spans, parent to child
    ex = by["execute"]
    assert ex.attrs["cache_hit"] is (batch == 1)
    assert by["cache"].attrs["hit"] is (batch == 1)
    run = by["run"]
    assert ex.t_start <= run.t_start and run.t_end <= ex.t_end
    assert run.attrs == {"call": "sweep"}
    assert [by[n].attrs["parent"] for n in RUN_SPANS[1:]] == \
        list(RUN_SPANS[:-1])
    for n in RUN_SPANS[1:]:
        assert run.t_start <= by[n].t_start and by[n].t_end <= run.t_end


@pytest.mark.parametrize("batch", [0, 1])
def test_batch_holds_its_construction_and_placement(served, batch):
    """Inside `build`: the runner's `construct` over the Simulator's (over
    `init_state` and `encode_trace`), then `place`, the [B, ...] inputs
    through the one sync a tracer buys; the ledger's spans under whichever
    span was open, and only in the batch that compiled."""
    spans = served["svc"].tracer.trace(f"batch-{batch}")
    setup = [s for s in spans if s.name in SETUP_SPANS
             and not s.name.startswith("jax_")]
    assert [(s.name, s.attrs["parent"]) for s in setup] == [
        ("init_state", "construct"), ("encode_trace", "construct"),
        ("construct", "construct"), ("construct", "build"),
        ("place", "build")]
    build = next(s for s in spans if s.name == "build")
    assert all(build.t_start <= s.t_start and s.t_end <= build.t_end
               for s in setup)
    assert [s.attrs["of"] for s in setup if s.name == "construct"] == \
        ["Simulator", "SweepRunner"]
    place = setup[-1]
    assert place.attrs["sims"] == 4 and place.attrs["bytes"] > 0
    assert place.attrs["programs"] >= 0
    ledger = [s for s in spans if s.name.startswith("jax_")]
    assert {s.attrs["parent"] for s in ledger} <= {
        "init_state", "encode_trace", "construct", "place", "build",
        "cache", "execute"}
    compiled = [s for s in ledger if s.name == "jax_compile"
                and "campaign_" in s.attrs["fun_name"]]
    assert len(compiled) == (1 if batch == 0 else 0)
    cache = next(s for s in spans if s.name == "cache")
    assert {"programs_compiled", "programs_loaded",
            "jax_compile_s"} <= set(cache.attrs)
    report_ = served["svc"].batch_log[batch]
    assert set(report_.programs) == set(ProgramLedger.COUNTERS)
    assert (report_.programs["programs_compiled"]
            + report_.programs["programs_loaded"] >= 1) is (batch == 0)


@pytest.mark.parametrize("s,lat", GRID)
def test_job_spans(served, s, lat):
    jid = f"s{s}-L{lat}"
    tracer = served["svc"].tracer
    spans = tracer.trace(jid)
    assert [x.name for x in spans] == \
        ["validate", "admit", "submit", "queue", "execute", "job", "emit"]
    assert set(x.name for x in spans) <= set(JOB_SPANS)
    by = {x.name: x for x in spans}
    batch = by["queue"].attrs["batch"]
    assert batch == (0 if s == 0 else 1) == by["job"].attrs["batch"]
    # queue: submit's end -> the batch formed; job: submit -> envelope
    assert by["submit"].t_end <= by["queue"].t_start
    (formed,) = [x for x in tracer.trace(f"batch-{batch}")
                 if x.name == "batch"]
    assert by["queue"].t_end == formed.t_start
    assert by["job"].t_start <= by["submit"].t_start
    assert by["job"].t_end >= by["execute"].t_end
    assert by["job"].dur_s > by["queue"].dur_s + by["execute"].dur_s
    assert tracer.missing_terminal([jid]) == []


@pytest.mark.parametrize("fmt", ["text", "json"])
def test_report_renders_a_served_grid(served, tmp_path, fmt):
    path = tmp_path / "spans.jsonl"
    assert served["svc"].export_spans(str(path)) > 0
    lines = report.render_spans(str(path), fmt)
    if fmt == "json":
        import json

        rows = [json.loads(ln) for ln in lines]
        job = next(r for r in rows if r.get("job") == "s0-L60")
        # `job` encloses the rest: the total is not their sum
        assert job["total_us"] == job["job_us"]
        return
    assert lines[0].split()[:8] == ["job"] + [n + "_us" for n in JOB_SPANS]
    head = next(ln for ln in lines if ln.split()[:1] == ["batch"])
    for n in BATCH_SPANS + RUN_SPANS:
        assert n + "_us" in head.split()


def test_resident_program_hooks(served):
    svc = served["svc"]
    prog = svc.resident_program()
    assert prog is not None and prog.name == svc.batch_log[-1].class_name
    assert CampaignService(batch_size=4, store=None,
                           n_devices=1).resident_program() is None
    # the last batch served: stream 1 at two latencies (+ 2 replicas)
    want = max(served["envelopes"][f"s1-L{lat}"].n_iterations
               for lat in (60, 180))
    assert prog.last_n_iterations == want and prog.last_run_dispatches == 1
    # the executable's own text, with no compile: tagged, and scoped
    before = len(served["compiles"])
    hlo = prog.compiled_text()
    assert len(served["compiles"]) == before
    assert f"campaign_{scopes.CACHE_TAG}" in hlo
    assert "gt.mem.sharer" in hlo and "gt.core.iocoom" in hlo
    # a tracer on the handle follows the program's next dispatch in a
    # `run-<n>` trace of its own; the service's tracer keeps the rest
    tracer = Tracer(clock=Clock())
    prog.attach_tracer(tracer)
    sc = SimConfig(ConfigFile.from_string(text()))
    svc.submit(Job(job_id="again", config=sc, trace=served["traces"][0],
                   knobs={"dram_latency_ns": 100}))
    (env,) = list(svc.drain())
    prog.attach_tracer(None)
    assert tracer.trace_ids() == ["run-0"]
    assert [s.name for s in tracer.spans] == \
        ["dispatch", "wait", "fetch", "results", "run"]
    last = svc.tracer.trace(f"batch-{env.batch_id}")
    assert not [s for s in last if s.name in RUN_SPANS]
    assert [s.name for s in last if s.name in BATCH_SPANS] == \
        list(BATCH_SPANS)
    assert len(served["compiles"]) == before
    np.testing.assert_array_equal(
        env.results.clock_ps, served["envelopes"]["s0-L100"].results.clock_ps)


def small_runner() -> SweepRunner:
    sc = SimConfig(ConfigFile.from_string(text()))
    return SweepRunner(sc, [stream(0), stream(1)],
                       [{"dram_latency_ns": 60}, {"dram_latency_ns": 180}],
                       shard_batch=False)


def test_sweep_runner_spans_and_trace_once(monkeypatch):
    from graphite_tpu.engine import step

    traced, made, synced = [], [], []
    real = step.run_simulation
    monkeypatch.setattr(step, "run_simulation",
                        lambda *a, **k: traced.append(1) or real(*a, **k))
    r = small_runner()
    closed, _ = r.lower(1_000_000)
    assert r._get_runner(1_000_000).__name__ == \
        f"campaign_{scopes.CACHE_TAG}"
    # without a tracer: no span, no extra device sync, and the program
    # lower() traced is not traced again by run()
    # (of the drive loop: `place` and what a first run compiles are
    # set-up's spans, always on, and sync nothing without a tracer)
    real_init = Span.__init__
    monkeypatch.setattr(
        Span, "__init__",
        lambda self, *a, **k: real_init(self, *a, **k) or (
            self.name in RUN_SPANS and made.append(1)) or None)
    real_sync = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: synced.append(1) or real_sync(x))
    plain = r.run()
    assert len(traced) == 1 and not made and not synced
    assert r.last_n_iterations == int(plain.n_iterations.max())
    assert r.last_run_dispatches == 1
    # with one: `run` > `dispatch` > `wait` > `fetch` > `results`, as
    # Simulator.run() records them, one block_until_ready, same results
    tracer = Tracer(clock=Clock())
    r.attach_tracer(tracer)
    again = r.run(trace_id="batch-7")
    r.attach_tracer(None)
    assert len(synced) == 1
    assert [(s.name, s.attrs.get("parent")) for s in tracer.spans] == [
        ("dispatch", "run"), ("wait", "dispatch"), ("fetch", "wait"),
        ("results", "fetch"), ("run", None)]
    assert tracer.trace_ids() == ["batch-7"]
    for a, b in zip(plain.results, again.results):
        np.testing.assert_array_equal(a.clock_ps, b.clock_ps)
    assert plain.phase_skips == again.phase_skips
    r.run()
    assert len(tracer.spans) == 5 and len(synced) == 1


def test_selfcheck_campaign():
    """`benchmark/selfcheck_campaign.py`: the cell's driver, judge and
    readers against tampered digests, envelopes and programs."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "selfcheck_campaign.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    assert "selfcheck_campaign: ok" in done.stdout
