"""Set-up accounts for itself (obs/trace.py: SETUP_SPANS, SetupSpans,
ProgramLedger): the spans of a constructed and warmed `Simulator` are
present, nested and bounded; a second `warmup()` traces, lowers, compiles
and loads nothing; the ledger tells a cache hit from a compile and folds
nested traces (its listener driven by hand: no chip, no compile); the
per-layer readers over them (benchmark/lib/setup_trace.py) give a number
on a recorded context and None on an empty one; every per-layer metric of
BENCHMARK.json has its file; `tools/report.py --spans` renders the table.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.obs import trace as obs_trace
from graphite_tpu.obs.trace import (
    PROGRAMS, SETUP, SETUP_SPANS, SETUP_TRACE_ID, ProgramLedger,
    SetupSpans, Span, Tracer,
)
from graphite_tpu.tools import report
from graphite_tpu.trace import synthetic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
try:
    from lib import paths, setup_trace
finally:
    sys.path.remove(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

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
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_retrieval_time_sec"


class Clock:
    """One second per reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def inside(child: Span, parent: Span) -> bool:
    return parent.t_start <= child.t_start and child.t_end <= parent.t_end


@pytest.fixture(scope="module")
def warmed():
    """One Simulator built and warmed with a tracer from the start."""
    tracer = Tracer(clock=Clock())
    before = len(SETUP.spans), SETUP.spans[-1] if SETUP.spans else None
    batch = synthetic.message_ring_batch(N, n_rounds=40,
                                         compute_per_round=11)
    sim = Simulator(SimConfig(ConfigFile.from_string(MAGIC)), batch,
                    tracer=tracer)
    sim.warmup()
    return {"sim": sim, "tracer": tracer, "before": before}


def test_spans_present_and_nested(warmed):
    tracer = warmed["tracer"]
    assert tracer.trace_ids() == [SETUP_TRACE_ID]
    spans = list(tracer.spans)
    assert {s.name for s in spans} <= set(SETUP_SPANS)
    by = {s.name: s for s in spans}
    assert {"construct", "init_state", "encode_trace", "warmup",
            "first_dispatch", "jax_trace", "jax_lower",
            "jax_compile"} <= set(by)
    assert by["construct"].attrs == {"of": "Simulator"}
    for child in ("init_state", "encode_trace"):
        assert by[child].attrs["parent"] == "construct"
        assert by[child].attrs["bytes"] > 0
        assert inside(by[child], by["construct"])
    assert by["init_state"].t_end <= by["encode_trace"].t_start
    assert by["first_dispatch"].attrs["parent"] == "warmup"
    assert inside(by["first_dispatch"], by["warmup"])
    assert by["construct"].t_end <= by["warmup"].t_start
    # the ledger's spans hang under the innermost span open: what the
    # state's arrays compiled under `init_state`, the run program under
    # `first_dispatch`, with the name JAX gave it
    ledger = [s for s in spans if s.name.startswith("jax_")]
    assert {s.attrs["parent"] for s in ledger} <= {
        "init_state", "encode_trace", "construct", "first_dispatch"}
    ran = [s for s in ledger if s.attrs["parent"] == "first_dispatch"]
    assert [s.name for s in ran] == ["jax_trace", "jax_lower", "jax_compile"]
    assert all("run_" in s.attrs["fun_name"] for s in ran)
    assert ran[0].attrs["nested"] > 0
    assert isinstance(ran[2].attrs["cache_hit"], bool)


def test_generator_spans_go_to_the_process_tracer(warmed):
    n_before, last_before = warmed["before"]
    new = list(SETUP.spans)
    if last_before in new:
        new = new[new.index(last_before) + 1:]
    built = [s for s in new if s.name == "build_trace"]
    assert built and built[0].attrs == {
        "generator": "message_ring_batch", "tiles": N,
        "records": built[0].attrs["records"]}
    assert built[0].attrs["records"] > N
    # the Simulator had a tracer: none of its spans went to SETUP
    assert not [s for s in new if s.name in ("construct", "warmup")]


def test_second_warmup_traces_lowers_compiles_and_loads_nothing(warmed):
    tracer, sim = warmed["tracer"], warmed["sim"]
    n = len(tracer.spans)
    counts = PROGRAMS.snapshot()
    sim.warmup()
    assert [s.name for s in list(tracer.spans)[n:]] == \
        ["first_dispatch", "warmup"]
    assert not any(PROGRAMS.since(counts).values())


@pytest.mark.parametrize("spmd", ["shard_map", "gspmd"])
def test_mesh_placement_is_a_place_span(monkeypatch, spmd):
    """Construction only (nothing compiles but the placement's own small
    programs): `place` inside `construct`, one device sync with a tracer,
    none without."""
    import jax

    from graphite_tpu.parallel.mesh import make_tile_mesh

    synced = []
    real_sync = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: synced.append(1) or real_sync(x))
    batch = synthetic.message_ring_batch(N, n_rounds=4, compute_per_round=3)
    config = SimConfig(ConfigFile.from_string(MAGIC))
    tracer = Tracer(clock=Clock())
    Simulator(config, batch, mesh=make_tile_mesh(N), spmd=spmd,
              tracer=tracer)
    by = {s.name: s for s in tracer.spans}
    place = by["place"]
    assert place.attrs["parent"] == "construct" and len(synced) == 1
    assert place.attrs["devices"] == N and place.attrs["bytes"] > 0
    assert by["encode_trace"].t_end <= place.t_start
    assert inside(place, by["construct"])
    Simulator(config, batch, mesh=make_tile_mesh(N), spmd=spmd)
    assert len(synced) == 1 and SETUP.spans[-1].name == "construct"
    assert [s for s in list(SETUP.spans)[-8:] if s.name == "place"]


def test_setup_trace_is_bounded():
    span = SetupSpans()
    assert span.tracer is SETUP and not span.on
    for _ in range(10_000):
        with span("construct"):
            pass
    assert len(SETUP.spans) == SETUP.spans.maxlen == 4096


def test_import_span_opens_the_process_trace():
    out = subprocess.run(
        [sys.executable, "-c",
         "import graphite_tpu; from graphite_tpu.obs.trace import SETUP;"
         "print([(s.name, s.dur_s > 0.1) for s in SETUP.spans])"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[('import', True)]"


# -- the ledger's listener, driven by hand ----------------------------------

def ledger_under(parent: str = "warmup"):
    """(a ledger on a hand-set clock, the tracer its spans go to, the
    open span's context manager)."""
    now = types.SimpleNamespace(t=100.0)
    ledger = ProgramLedger(clock=lambda: now.t)
    tracer = Tracer(clock=lambda: now.t)
    return now, ledger, tracer, SetupSpans(tracer)(parent)


def test_ledger_tells_a_compile_from_a_load():
    now, ledger, tracer, under = ledger_under()
    with under:
        # a miss, or a program the cache is not asked about
        ledger.on_event(TRACE, 2.0, fun_name="f")
        ledger.on_event(LOWER, 1.0, fun_name="jit(f)")
        ledger.on_event(COMPILE, 5.0, fun_name="jit(f)")
        # a hit: the retrieval's duration comes just before
        ledger.on_event(TRACE, 2.0, fun_name="g")
        ledger.on_event(LOWER, 1.0, fun_name="jit(g)")
        ledger.on_event(HIT, 0.5)
        ledger.on_event(COMPILE, 0.75, fun_name="jit(g)")
        # the hit does not outlive its program
        ledger.on_event(COMPILE, 0.25, fun_name="jit(h)")
        ledger.on_event("/jax/some/other/event", 9.0)
        got = ledger.snapshot()
    assert got == {"programs_traced": 2, "trace_s": 4.0,
                   "programs_lowered": 2, "lower_s": 2.0,
                   "programs_compiled": 2, "compile_s": 5.25,
                   "programs_loaded": 1, "load_s": 0.75}
    made = [(s.name, s.attrs["fun_name"], s.attrs.get("cache_hit"),
             s.attrs["parent"], s.dur_s, s.t_end) for s in tracer.spans
            if s.name != "warmup"]
    assert made == [
        ("jax_trace", "f", None, "warmup", 2.0, 100.0),
        ("jax_lower", "jit(f)", None, "warmup", 1.0, 100.0),
        ("jax_compile", "jit(f)", False, "warmup", 5.0, 100.0),
        ("jax_trace", "g", None, "warmup", 2.0, 100.0),
        ("jax_lower", "jit(g)", None, "warmup", 1.0, 100.0),
        ("jax_compile", "jit(g)", True, "warmup", 0.75, 100.0),
        ("jax_compile", "jit(h)", False, "warmup", 0.25, 100.0)]
    assert ledger.since(dict.fromkeys(got, 0)) == got
    assert not any(ledger.since(got).values())


def test_ledger_folds_nested_traces_into_the_outermost():
    now, ledger, tracer, under = ledger_under("first_dispatch")
    with under:
        now.t = 100.5
        ledger.on_event(TRACE, 0.25, fun_name="where")    # 100.25-100.5
        now.t = 101.0
        ledger.on_event(TRACE, 0.25, fun_name="sort")     # 100.75-101
        now.t = 102.0
        ledger.on_event(TRACE, 2.0, fun_name="run")       # 100-102: outer
        now.t = 104.0
        ledger.on_event(TRACE, 1.0, fun_name="other")     # 103-104: apart
        assert not [s for s in tracer.spans]              # not yet known
        got = ledger.snapshot()
    assert (got["programs_traced"], got["trace_s"]) == (2, 3.0)
    assert [(s.attrs["fun_name"], s.attrs["nested"], s.t_start, s.t_end)
            for s in tracer.spans if s.name == "jax_trace"] == [
        ("run", 2, 100.0, 102.0), ("other", 0, 103.0, 104.0)]


def test_ledger_without_an_open_span_goes_to_the_process_tracer():
    ledger = ProgramLedger()
    n = len(SETUP.spans)
    ledger.on_event(COMPILE, 0.5, fun_name="jit(lonely)")
    last = SETUP.spans[-1]
    assert (last.trace_id, last.name, last.attrs) == (
        SETUP_TRACE_ID, "jax_compile",
        {"fun_name": "jit(lonely)", "cache_hit": False})
    assert len(SETUP.spans) in (n + 1, SETUP.spans.maxlen)


def test_a_span_inside_another_joins_its_trace():
    tracer = Tracer(clock=Clock())
    with SetupSpans(tracer, "batch-7")("build", batch=7):
        inner = SetupSpans(tracer)
        assert inner.trace_id == "batch-7" and inner.on
        with inner("construct"):
            # another tracer's spans keep their own trace, and the parent
            assert SetupSpans().trace_id == SETUP_TRACE_ID
    assert SetupSpans(tracer).trace_id == SETUP_TRACE_ID
    assert [(s.trace_id, s.name, s.attrs.get("parent"))
            for s in tracer.spans] == [("batch-7", "construct", "build"),
                                       ("batch-7", "build", None)]


# -- the per-layer readers --------------------------------------------------

def recorded(monkeypatch, *, served: bool = False):
    """A context over a hand-recorded set-up: import 0-3, build_trace 3-4,
    construct 4-10 over init_state 5-8 (a compile inside, 6-7) and
    encode_trace 8-9, warmup = first_dispatch 10-20 over a trace 10-12, a
    lowering 12-13 and a load 13-18; the window starts at 21, and a
    compile at 22-23 is not set-up's."""
    setup = Tracer(clock=lambda: 0.0)
    for name, a, b, attrs in (
            ("import", 0, 3, {}), ("build_trace", 3, 4, {}),
            ("jax_compile", 6, 7, {"cache_hit": False}),
            ("init_state", 5, 8, {}), ("encode_trace", 8, 9, {}),
            ("construct", 4, 10, {}), ("jax_trace", 10, 12, {}),
            ("jax_lower", 12, 13, {}),
            ("jax_compile", 13, 18, {"cache_hit": True}),
            ("first_dispatch", 10, 20, {}), ("warmup", 10, 20, {}),
            ("jax_compile", 22, 23, {"cache_hit": False})):
        setup.record(SETUP_TRACE_ID, name, a, b, **attrs)
    monkeypatch.setattr(obs_trace, "SETUP", setup)
    ctx = types.SimpleNamespace(readings=[{"t0": 21.0}], own={})
    if served:
        # batch 0 is set-up's (8 s, 2 of them a compile under `execute`);
        # batch 2 serves the window and places its inputs in 4 ms
        tracer = Tracer(clock=lambda: 0.0)
        tracer.record("batch-0", "build", 30, 32)
        tracer.record("batch-0", "jax_compile", 33, 35, cache_hit=False)
        tracer.record("batch-0", "execute", 32, 38)
        tracer.record("batch-0", "batch", 29, 39)
        tracer.record("batch-2", "place", 50, 50.004, parent="build")
        env = types.SimpleNamespace(status="ok", batch_id=2)
        ctx.readings[0]["jobs"] = [{"envelopes": [env]}]
        ctx.own["svc"] = types.SimpleNamespace(tracer=tracer, batch_log=())
    return ctx


SOLO = {"import_s": 3.0, "trace_build_s": 2.0, "state_place_s": 2.0,
        "lower_s": 3.0, "program_load_s": 5.0, "program_compile_s": 1.0,
        "programs_compiled": 1, "setup_traced_s": 20.0,
        "batch_place_ms": None}
SERVED = {**SOLO, "program_compile_s": 3.0, "programs_compiled": 2,
          "setup_traced_s": 28.0, "batch_place_ms": 4.0}
ENTRY = [m for m in MANIFEST["per_layer"] if m["name"] in SOLO]


def read(name: str, ctx):
    sys.path.insert(0, BENCH)
    try:
        return paths.load_module("layer_metrics", name).read(ctx)
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("name", sorted(SOLO))
def test_reader_on_a_recorded_context(monkeypatch, capsys, name):
    for served, want in ((False, SOLO), (True, SERVED)):
        got = read(name, recorded(monkeypatch, served=served))
        assert got == (None if want[name] is None
                       else pytest.approx(want[name])), (served, got)
    out = capsys.readouterr().out
    if name != "batch_place_ms":
        # the spans it summed, longest first, then the verdict
        assert "setup-trace jax_compile.loaded 5.000000 1 5.000000" in out
        assert "setup-trace first_dispatch 2.000000 1 10.000000" in out
        assert "programs_compiled 1 " in out and "programs_loaded 1 " in out


@pytest.mark.parametrize("name", sorted(SOLO))
def test_reader_gives_none_where_nothing_is_recorded(monkeypatch, name):
    # no reading, no set-up span, and a program from before the spans
    ctx = recorded(monkeypatch)
    ctx.readings = []
    assert read(name, ctx) is None
    empty = types.SimpleNamespace(readings=[{"t0": 21.0}], own={})
    monkeypatch.setattr(obs_trace, "SETUP", Tracer())
    assert read(name, empty) is None
    monkeypatch.delattr(obs_trace, "SETUP")
    assert read(name, types.SimpleNamespace(
        readings=[{"t0": 21.0}], own={})) is None


def test_the_parts_sum_to_the_traced_time(monkeypatch):
    red = setup_trace.get(recorded(monkeypatch))
    assert sum(red["exclusive_s"].values()) == pytest.approx(
        red["traced_s"]) == pytest.approx(20.0)
    assert red["exclusive_s"]["construct"] == pytest.approx(2.0)
    assert red["inclusive_s"]["init_state"] == pytest.approx(3.0)
    assert red["count"][setup_trace.COMPILED] == 1


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_every_per_layer_metric_has_its_file(metric):
    path = os.path.join(BENCH, "layer_metrics", metric["name"] + ".py")
    assert os.path.exists(path), path
    with open(path) as f:
        assert "def read(ctx)" in f.read()


def test_the_entry_metrics_are_listed_as_the_issue_orders():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert len(ENTRY) == len(SOLO) == 9
    # appended together, in order (later PRs append behind them)
    first = MANIFEST["per_layer"].index(ENTRY[0])
    assert MANIFEST["per_layer"][first:first + 9] == ENTRY
    for m in ENTRY:
        if m["name"] == "batch_place_ms":
            assert (m["layer"], m["moves"], m["workloads"]) == (
                "service - serve/service.py, sweep/runner.py",
                "sim_records_per_s",
                ["campaign64-dram", "vfsweep256-canneal"])
            continue
        assert (m["layer"], m["moves"], m["better"], m["workloads"]) == (
            "entry - Simulator.warmup()", "setup_s", "lower", cells)
        assert m["source"] == ("program_counter" if m["unit"] == "count"
                               else "program_span")


# -- tools/report.py --spans ------------------------------------------------

@pytest.mark.parametrize("fmt", ["text", "json"])
def test_report_renders_the_setup_trace(warmed, tmp_path, fmt):
    path = tmp_path / "spans.jsonl"
    warmed["tracer"].export_jsonl(str(path))
    lines = report.render_spans(str(path), fmt)
    if fmt == "json":
        rows = [json.loads(ln) for ln in lines]
        top = [r["setup"]["span"] for r in rows if "setup" in r]
        assert top[:2] == ["construct", "warmup"]
        (programs,) = [r["programs"] for r in rows if "programs" in r]
        assert programs["jax_lower"][0] >= 1
        assert not [r for r in rows if r.get("job") == SETUP_TRACE_ID]
        return
    head = next(i for i, ln in enumerate(lines)
                if ln.split()[:1] == ["setup"])
    assert lines[head].split() == ["setup", "trace", "count", "start_us",
                                   "dur_us", "self_us"]
    construct = lines[head + 1].split()
    assert construct[:3] == ["construct", SETUP_TRACE_ID, "1"]
    # self time = duration minus the children inside it
    assert 0 <= int(construct[5]) < int(construct[4])
    assert any(ln.split()[:1] == ["programs"] for ln in lines)
