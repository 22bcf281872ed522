"""The drive loop's own spans and launch counters (`Simulator.attach_tracer`,
obs/trace.py RunSpans): exact span sets on a fake clock for every way the
loop is driven, results bit-equal with and without a tracer, nothing
created and no extra device sync without one, and the spans rendered by
`tools/report.py --spans` with no change to the exporter.
"""

import math

import jax
import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.obs import trace as obs_trace
from graphite_tpu.obs.trace import RUN_SPANS, Span, Tracer
from graphite_tpu.tools import report
from graphite_tpu.trace import synthetic

N = 4
MAGIC = """
[general]
total_cores = 4
mode = lite
max_frequency = 1.0
enable_shared_mem = false
[network]
user = magic
memory = magic
[core/static_instruction_costs]
ialu = 1
[clock_skew_management]
scheme = lax_barrier
[clock_skew_management/lax_barrier]
quantum = 100
"""


class Clock:
    """One second per reading: a span's edges are its two readings."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def make(**kw) -> Simulator:
    batch = synthetic.message_ring_batch(N, n_rounds=40,
                                         compute_per_round=11)
    return Simulator(SimConfig(ConfigFile.from_string(MAGIC)), batch, **kw)


def traced(sim) -> Tracer:
    tracer = Tracer(clock=Clock())
    sim.attach_tracer(tracer)
    return tracer


def shape(tracer):
    """[(name, parent, batch-or-window index)] in the order spans ended,
    after checking that every span is closed and all share one trace."""
    spans = list(tracer.spans)
    assert all(not s.open and s.t_end > s.t_start for s in spans)
    assert len({s.trace_id for s in spans}) == 1
    return [(s.name, s.attrs.get("parent"),
             s.attrs.get("batch", s.attrs.get("window"))) for s in spans]


def equal(a, b, quanta: bool = True) -> bool:
    """Every statistic of the two results (a streamed run counts quanta
    per window, so there `quanta=False`)."""
    return all(np.array_equal(getattr(a, f), getattr(b, f)) for f in (
        "clock_ps", "instruction_count", "recv_stall_ps", "sync_stall_ps",
        "packets_sent", "packets_received", "total_packet_latency_ps")) \
        and (a.n_quanta == b.n_quanta or not quanta) \
        and a.completion_time_ps == b.completion_time_ps


def test_single_region_run():
    plain = make().run()
    sim = make()
    tracer = traced(sim)
    res = sim.run()
    assert shape(tracer) == [
        ("dispatch", "run", None), ("wait", "dispatch", None),
        ("fetch", "wait", None), ("results", "fetch", None),
        ("run", None, None)]
    assert tracer.trace_ids() == ["run-0"]
    run = tracer.trace("run-0")[-1]
    assert run.attrs == {"call": "run"}
    # the fake clock: ten readings, `run` holds them all
    assert (run.t_start, run.t_end) == (1.0, 10.0)
    assert [s.dur_s for s in tracer.spans] == [1.0, 1.0, 1.0, 1.0, 9.0]
    assert equal(res, plain)
    assert sim.last_run_dispatches == 1 and sim.n_dispatches == 1


def test_host_barrier_run_spans_every_batch():
    plain = make().run()
    sim = make(barrier_host=True, barrier_batch=2)
    tracer = traced(sim)
    res = sim.run()
    assert equal(res, plain)
    batches = math.ceil(res.n_quanta / sim.barrier_batch)
    assert batches >= 2, "the target must take several batches"
    assert sim.last_run_dispatches == batches == sim.n_dispatches
    want = []
    for b in range(batches):
        want += [("dispatch", "run", b), ("wait", "dispatch", b),
                 ("fetch", "wait", b)]
    want += [("fetch", "run", None), ("results", "fetch", None),
             ("run", None, None)]
    assert shape(tracer) == want
    fetches = [s for s in tracer.spans
               if s.name == "fetch" and "batch" in s.attrs]
    assert sum(s.attrs["quanta"] for s in fetches) == res.n_quanta
    assert sum(s.attrs["iterations"] for s in fetches) \
        == sim.last_n_iterations


def test_run_chunk():
    sim = make()
    tracer = traced(sim)
    done, nq = sim.run_chunk(2)
    assert not done and nq == 2
    assert shape(tracer) == [
        ("dispatch", "run", None), ("wait", "dispatch", None),
        ("fetch", "wait", None), ("run", None, None)]
    assert tracer.trace("run-0")[-1].attrs == {"call": "run_chunk"}
    # a chunk is no completed run
    assert sim.last_run_dispatches == 0 and sim.n_dispatches == 1
    sim.run_chunk(2, trace_id="job-7")
    assert tracer.trace_ids() == ["run-0", "job-7"]
    assert sim.n_dispatches == 2


def test_run_chunk_host_barrier():
    sim = make(barrier_host=True, barrier_batch=2)
    tracer = traced(sim)
    sim.run_chunk(3)
    assert shape(tracer) == [
        ("dispatch", "run", 0), ("wait", "dispatch", 0), ("fetch", "wait", 0),
        ("dispatch", "run", 1), ("wait", "dispatch", 1), ("fetch", "wait", 1),
        ("run", None, None)]
    assert [s.attrs["quanta"] for s in tracer.spans
            if s.name == "fetch"] == [2, 1]
    assert sim.last_run_dispatches == 0 and sim.n_dispatches == 2


def test_run_streamed():
    plain = make().run()
    sim = make(stream=True)
    tracer = traced(sim)
    res = sim.run_streamed(window_records=16)
    assert equal(res, plain, quanta=False)
    got = shape(tracer)
    windows = sim.n_dispatches
    assert windows >= 2, "the trace must take several windows"
    assert got[0] == ("refill", "run", 0)
    assert got[-3:] == [("fetch", "run", None), ("results", "fetch", None),
                        ("run", None, None)]
    for name in ("dispatch", "wait", "fetch"):
        assert [i for n, _, i in got if n == name and i is not None] \
            == list(range(windows))
    assert {n for n, _, _ in got} == set(RUN_SPANS) | {"refill"}
    assert tracer.trace("run-0")[-1].attrs == {"call": "run_streamed"}


def test_trace_ids_count_per_tracer():
    tracer = Tracer(clock=Clock())
    for _ in range(2):
        sim = make()
        sim.attach_tracer(tracer)
        sim.run()
    assert tracer.trace_ids() == ["run-0", "run-1"]


def test_tracer_from_construction_is_bit_equal(monkeypatch):
    """`Simulator(tracer=)`: the set-up spans go to the caller's tracer
    under `setup`, the run's under `run-0`, and no statistic moves."""
    plain = make().run()
    tracer = Tracer(clock=Clock())
    sim = make(tracer=tracer)
    assert sim.tracer is tracer
    sim.warmup()
    res = sim.run()
    assert equal(res, plain)
    assert tracer.trace_ids() == [obs_trace.SETUP_TRACE_ID, "run-0"]
    assert {s.name for s in tracer.trace(obs_trace.SETUP_TRACE_ID)} == {
        "construct", "init_state", "encode_trace", "warmup",
        "first_dispatch", "jax_trace", "jax_lower", "jax_compile"}
    assert [s.name for s in tracer.trace("run-0")] == [
        "dispatch", "wait", "fetch", "results", "run"]
    # host-driven too
    hb = make(barrier_host=True, barrier_batch=2, tracer=Tracer())
    hb.warmup()
    assert equal(hb.run(), plain)


def test_no_tracer_no_span_no_sync(monkeypatch):
    """Without a tracer `run()` / `run_chunk()` / `run_streamed()` make no
    span of the drive loop and no device sync; what a run compiles on its
    first call is the program ledger's to record (always on, set-up's)."""
    made, synced = [], []
    real_init = Span.__init__

    def counting_init(self, *a, **k):
        if (k.get("name") or a[1]) in RUN_SPANS + ("refill",):
            made.append(1)
        real_init(self, *a, **k)

    monkeypatch.setattr(Span, "__init__", counting_init)
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: synced.append(1) or x)
    make().run()
    make(barrier_host=True, barrier_batch=2).run()
    make().run_chunk(2)
    make(stream=True).run_streamed(window_records=16)
    assert not made and not synced
    # and with one, one `wait` per dispatch
    sim = make(barrier_host=True, barrier_batch=2)
    traced(sim)
    sim.run()
    assert len(synced) == sim.n_dispatches and made


def test_detach():
    sim = make()
    tracer = traced(sim)
    sim.attach_tracer(None)
    sim.run()
    assert not tracer.spans


@pytest.mark.parametrize("fmt", ["text", "json"])
def test_report_renders_a_run_trace(tmp_path, fmt):
    sim = make(barrier_host=True, barrier_batch=2)
    tracer = traced(sim)
    sim.run()
    path = tmp_path / "spans.jsonl"
    assert tracer.export_jsonl(str(path)) == len(tracer.spans)
    lines = report.render_spans(str(path), fmt)
    if fmt == "json":
        import json

        row = json.loads(lines[0])
        assert row["job"] == "run-0"
        # `run` encloses the rest: the total is not their sum
        assert row["total_us"] == row["run_us"]
        return
    header = lines[0].split()
    assert header == ["job"] + [s + "_us" for s in RUN_SPANS] \
        + ["total_us", "status"]
    assert lines[1].split()[0] == "run-0"


def test_perfetto_export_takes_run_spans(tmp_path):
    sim = make()
    tracer = traced(sim)
    sim.run()
    path = tmp_path / "spans.jsonl"
    tracer.export_jsonl(str(path))
    events = report.perfetto_events(spans=str(path))
    names = [e["name"] for e in events if e["ph"] == "X"]
    assert sorted(names) == sorted(RUN_SPANS)
    assert {e["tid"] for e in events if e["ph"] == "X"} == {"run-0"}


def test_annotations_are_named_for_the_profiler(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    sim = make()
    traced(sim)
    sim.run()
    assert seen == [obs_trace.ANNOTATION_PREFIX + n for n in
                    ("run", "dispatch", "wait", "fetch", "results")]
