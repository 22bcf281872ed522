"""benchmark/lib/scope_trace.py against a recorded trace: a few hundred
events of a real traced `run()` on the v5e with the `op_name`s of their
instructions (`benchmark/lib/recorded_scope_trace.json`, which also holds
the reduction worked once by hand).  No chip and no traced program is
needed: the reduction is plain Python over the recorded events.
"""

import json
import os
import sys

import pytest

from graphite_tpu.obs import scopes

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)

from lib import scope_trace  # noqa: E402

with open(os.path.join(BENCH, "lib", "recorded_scope_trace.json")) as f:
    REC = json.load(f)
EXPECTED = REC["expected"]


@pytest.fixture(scope="module")
def red():
    return scope_trace.reduce(REC["trace"], REC["op_names"], scopes.deepest,
                              REC["iterations"])


def test_totals(red):
    assert red["scoped"] is True
    assert red["leaf_events"] == EXPECTED["leaf_events"]
    assert red["busy_total_s"] == pytest.approx(
        EXPECTED["busy_total_ns"] / 1e9, rel=1e-12)
    assert red["window_s"] == pytest.approx(
        EXPECTED["window_ns"] / 1e9, rel=1e-12)
    assert set(red["busy_s"]) == set(EXPECTED["busy_ns"])


@pytest.mark.parametrize("scope", sorted(EXPECTED["busy_ns"]))
def test_busy_time_and_count_of_each_scope(red, scope):
    assert red["busy_s"][scope] == pytest.approx(
        EXPECTED["busy_ns"][scope] / 1e9, rel=1e-12)
    assert red["ops"][scope] == EXPECTED["ops"][scope]
    assert scope_trace.shares(red)[scope] == pytest.approx(
        EXPECTED["shares"][scope], rel=1e-9)


@pytest.mark.parametrize("where", sorted(EXPECTED["gaps_ns"]))
def test_device_empty_time_by_host_span(red, where):
    assert red["gaps"][where] == pytest.approx(
        EXPECTED["gaps_ns"][where] / 1e9, rel=1e-12)


def test_gaps_and_programs_fill_the_window(red):
    modules = [e for p in REC["trace"]["planes"] for line in p["lines"]
               if line["name"] == "XLA Modules" for e in line["events"]]
    in_program = sum(d for _, _, d in modules) / 1e9
    assert sum(red["gaps"].values()) + in_program == pytest.approx(
        red["window_s"], rel=1e-9)


def test_shares_sum_to_100(red):
    sh = scope_trace.shares(red)
    assert sum(sh.values()) == pytest.approx(100.0, abs=1e-6)
    groups = [
        lambda s: s == "gt.fetch" or s.startswith("gt.core"),
        lambda s: s.startswith("gt.net."),
        lambda s: s.startswith("gt.sync."),
        lambda s: s.startswith("gt.mem.")
        and s not in scope_trace.MEM_UNGATED,
        lambda s: s in scope_trace.MEM_UNGATED,
        lambda s: s == scope_trace.UNSCOPED,
        lambda s: s in ("gt.quantum", "gt.obs", "gt.px"),
        lambda s: s in ("gt.dvfs", "gt.energy"),    # `dvfs_busy_share`
    ]
    # the seven metrics' groups and the three printed-only scopes part the
    # registry: every scope is in exactly one
    for name in scopes.SCOPES + (scope_trace.UNSCOPED,):
        assert sum(bool(g(name)) for g in groups) == 1, name
    assert sum(v for k, v in sh.items() for g in groups if g(k)) \
        == pytest.approx(100.0, abs=1e-6)


def test_top_operations_carry_no_instruction_number(red):
    assert len(red["top"]) == 10
    for label, seconds in red["top"]:
        scope, kind = label.split(" · ")[:2]
        assert scope in scopes.SCOPES + (scope_trace.UNSCOPED,)
        assert "." not in kind and "%" not in label and seconds > 0
    assert [s for _, s in red["top"]] == sorted(
        (s for _, s in red["top"]), reverse=True)
    assert red["unscoped_kinds"][0][1] >= red["unscoped_kinds"][-1][1]


def test_table_lines(red):
    lines = scope_trace.table(red)
    kinds = {ln.split(" ")[0] for ln in lines}
    assert kinds == {"scope", "top", "unscoped", "gap"}
    first = lines[0].split(" ")
    assert first[0] == "scope" and len(first) == 5
    shares = [float(ln.split(" ")[3]) for ln in lines
              if ln.startswith("scope ")]
    assert sum(shares) == pytest.approx(100.0, abs=0.1)
    assert any(ln.startswith("gap gt:dispatch ") for ln in lines)


def test_executable_without_scopes_names_nothing():
    """The cache trap: an executable compiled before the scopes existed
    has `op_name`s, and none of them holds a registered name."""
    stripped = {k: "/".join(seg for seg in v.split("/")
                            if not seg.startswith("gt."))
                for k, v in REC["op_names"].items()}
    assert not any("gt." in v for v in stripped.values())
    red = scope_trace.reduce(REC["trace"], stripped, scopes.deepest,
                             REC["iterations"])
    assert red["scoped"] is False
    assert red["busy_s"] is None and red["ops"] is None \
        and red["top"] is None
    assert scope_trace.shares(red) is None
    # the host's spans and the device-empty time do not need scopes
    assert red["gaps"]["gt:dispatch"] == pytest.approx(
        EXPECTED["gaps_ns"]["gt:dispatch"] / 1e9, rel=1e-12)
    assert red["leaf_events"] == EXPECTED["leaf_events"]
    assert all(ln.startswith("gap ") for ln in scope_trace.table(red))


def test_no_op_names_at_all_reads_as_unscoped_program():
    red = scope_trace.reduce(REC["trace"], {}, scopes.deepest)
    assert red["scoped"] is False and scope_trace.shares(red) is None


def test_trace_without_a_tpu_is_refused():
    host_only = {"planes": [p for p in REC["trace"]["planes"]
                            if not p["name"].startswith("/device")]}
    with pytest.raises(ValueError, match="no TPU operation"):
        scope_trace.reduce(host_only, REC["op_names"], scopes.deepest)


def test_op_names_from_hlo_text():
    text = "\n".join([
        "HloModule jit_run",
        "%fused_computation.1 (p: u32[64]) -> u32[64] {",
        '  ROOT %add.3 = u32[64]{0} add(%p, %p), metadata={op_name="jit('
        'run)/gt.quantum/while/body/gt.core/add" stack_frame_id=2}',
        "}",
        '  %fusion.7 = u32[64]{0:T(512)} fusion(%x), kind=kLoop, calls='
        '%fused_computation.1, metadata={op_name="jit(run)/gt.quantum/'
        'while/body/gt.core/gt.net.mailbox/cond/branch_1_fun/add"}',
        "  %copy-start.2 = (u32[64], u32[64], u32[]) copy-start(%x)",
        "  %copy-done.2 = u32[64]{0} copy-done(%copy-start.2)",
    ])
    names = scope_trace.op_names(text)
    assert set(names) == {"add.3", "fusion.7"}
    assert scopes.deepest(names["fusion.7"]) == "gt.net.mailbox"


def test_an_instruction_xla_made_inherits_what_it_wraps():
    text = "\n".join([
        "%fused_computation.1 (p: u32[64]) -> u32[64] {",
        '  %mul.1 = u32[64]{0} multiply(%p, %p), metadata={op_name="jit('
        'run)/gt.quantum/while/body/gt.core/gt.mem.base/mul"}',
        "  ROOT %copy.9 = u32[64]{0} copy(%mul.1)",
        "}",
        "%async_computation (q: u32[64]) -> u32[16] {",
        '  ROOT %slice.1 = u32[16]{0} slice(%q), slice={[0:16]}, metadata='
        '{op_name="jit(run)/gt.quantum/while/body/gt.core/gt.fetch/slice"}',
        "}",
        "ENTRY %main (x: u32[64]) -> u32[64] {",
        "  %fusion.7 = u32[64]{0} fusion(%x), kind=kLoop, "
        "calls=%fused_computation.1",
        "  %slice-start = ((u32[64]), u32[16], s32[]) async-start(%x), "
        "calls=%async_computation",
        "  %slice-done = u32[16]{0} async-done(((u32[64]), u32[16], s32[]) "
        "%slice-start)",
        "  %copy-start.2 = (u32[64], u32[64], u32[]) copy-start(%x)",
        "  %copy-done.2 = u32[64]{0} copy-done(%copy-start.2)",
        "}"])
    got = {k: scopes.deepest(v)
           for k, v in scope_trace.op_names(text).items()}
    assert got == {"mul.1": "gt.mem.base", "slice.1": "gt.fetch",
                   "fusion.7": "gt.mem.base", "slice-start": "gt.fetch",
                   "slice-done": "gt.fetch"}


def test_span_readers():
    class Ctx:
        own = {scope_trace._KEY: {"spans": REC["spans"]}}

    dispatch = [r["dur_us"] for r in REC["spans"] if r["span"] == "dispatch"]
    assert scope_trace.span_ms(Ctx, "dispatch", mean=True) \
        == pytest.approx(sum(dispatch) / len(dispatch) / 1e3)
    both = sum(r["dur_us"] for r in REC["spans"]
               if r["span"] in ("fetch", "results"))
    assert scope_trace.span_ms(Ctx, "fetch", "results") \
        == pytest.approx(both / 1e3)
    assert scope_trace.span_ms(Ctx, "refill") is None
    Ctx.own = {scope_trace._KEY: None}
    assert scope_trace.span_ms(Ctx, "dispatch") is None
    assert scope_trace.share(Ctx, lambda s: True) is None
