"""Static cost & residency model over lowered programs, with budgets.

PR 3's auditor checks *structural* invariants of the lowered jaxpr;
nothing measured what a program *costs* until it ran on hardware we
rarely have.  This module is the static counterpart to a run: walking
the same `jax.make_jaxpr` artifacts `Simulator.lower()` /
`SweepRunner.lower()` expose (via the analysis/walk.py traversal), it
computes

  per-eqn bytes      operand + result bytes of every equation, with
                     loop trip-count multipliers (scan lengths are
                     static; while bodies count once — the
                     per-iteration view the op-tail floor lives in);
  kernel proxy       per-protocol-iteration equation count, attributed
                     per phase via the round-6 phase-cond structure
                     (rules.phase_conds) — eqns >= fused kernels, but
                     the count moves monotonically with the op tail
                     the config-5 ~0.2 ms floor is made of;
  peak residency     a live-range scan over the program: vars become
                     live at definition, die after last use; cond/while
                     outputs are counted ON TOP of their live operands
                     (XLA double-buffers them — the round-6 pathology).
                     Ignores buffer donation/aliasing and fusion, so it
                     is an over-estimate; `backend_memory_comparison`
                     records the deviation from the backend's own
                     `compiled.memory_analysis()` where available.

On top sits the budget layer: `BUDGETS.json` holds a measured baseline
and slack-derived ceiling per audited program; `check_budget` fails when
any metric exceeds its ceiling, naming the largest-contributing equation
— so a layout mistake (round 4's 10.7 GB temp inflation) or an op-tail
regression is caught in tier-1 CI, statically, with no TPU.

Residency is budgeted once, in one place: `residency_breakdown` itemizes
the HBM consumers ROADMAP lists (per-sim state x B, resident campaign
traces, telemetry rings, streaming windows), `ResidencyBudgetError` is
the ONE exception type every residency refusal raises (SweepRunner's
pre-compile fail-fast, attach_telemetry's stream/mesh rejections), and
its message always carries the per-consumer breakdown.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
from jax.extend.core import Literal
import numpy as np

from graphite_tpu.analysis.walk import (
    _DIRECT_CALLS, as_jaxpr, aval_bytes, iter_eqns, iter_eqns_with_site,
    subjaxprs,
)


class ResidencyBudgetError(ValueError):
    """A residency budget refused a program layout.

    The one exception type for every HBM-residency refusal — the
    SweepRunner pre-compile fail-fast and attach_telemetry's
    stream/mesh rejections both raise it, and the message always
    includes the analyzer's per-consumer breakdown
    (`residency_breakdown` / `format_breakdown`).  Subclasses
    ValueError: callers that treated the old refusals as value errors
    keep working.
    """


# ---------------------------------------------------------------------------
# per-consumer residency model
# ---------------------------------------------------------------------------


def tree_bytes(tree) -> int:
    """Total bytes of a pytree's array leaves (concrete arrays, numpy
    arrays, or ShapeDtypeStructs — anything with .shape/.dtype)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += aval_bytes(leaf)
    return total


def trace_record_bytes(trace) -> int:
    """Bytes of ONE record across every field of a trace dataclass
    (TraceBatch or any per-record array bundle) — the per-record unit
    the streaming-window bound (Simulator.residency_breakdown) and the
    campaign service's admission bill both price from.  One definition,
    so adding or retyping a trace field moves every residency estimate
    together."""
    return int(sum(
        np.dtype(np.asarray(getattr(trace, f.name)).dtype).itemsize
        for f in dataclasses.fields(trace)))


def residency_breakdown(*, state=None, trace=None, batch: int = 1,
                        telemetry_spec=None, profile_spec=None,
                        hist_spec=None,
                        stream_window_bytes: "int | None" = None,
                        ) -> "dict[str, int]":
    """Itemized HBM residency estimate, bytes per consumer.

    `state`: one sim's state pytree (multiplied by `batch` — a campaign
    broadcasts B copies).  `trace`: the RESIDENT trace pytree — for a
    campaign pass the packed [B, T, L] arrays (already batch-shaped, so
    NOT multiplied).  `telemetry_spec`: a resolved obs.TelemetrySpec
    whose ring rides each sim's carry (x batch).  `profile_spec`: a
    resolved obs.ProfileSpec whose [S, T, m] per-tile ring rides each
    sim's carry (x batch).  `hist_spec`: a resolved obs.HistSpec whose
    [(T,) H, B] bucket-count ring rides each sim's carry (x batch).
    `stream_window_bytes`:
    the host->HBM window bound of a streaming run.  Returns consumer ->
    bytes plus a "total" key.  The while-carry double-buffer is NOT
    applied here (it is program-dependent); `CostReport.peak_bytes` is
    the program-level estimate that includes it.
    """
    out: "dict[str, int]" = {}
    if state is not None:
        out["state"] = int(tree_bytes(state)) * int(batch)
    if trace is not None:
        out["trace"] = int(tree_bytes(trace))
    if telemetry_spec is not None:
        out["telemetry"] = int(telemetry_ring_bytes(telemetry_spec)) \
            * int(batch)
    if profile_spec is not None:
        out["profile"] = int(profile_ring_bytes(profile_spec)) \
            * int(batch)
    if hist_spec is not None:
        out["hist"] = int(hist_ring_bytes(hist_spec)) * int(batch)
    if stream_window_bytes is not None:
        out["stream_window"] = int(stream_window_bytes)
    out["total"] = sum(out.values())
    return out


def device_residency_breakdown(*, state=None, state_split=None,
                               sims_per_shard: int = 1,
                               tile_shards: int = 1,
                               per_sim_trace_bytes: int = 0,
                               telemetry_spec=None,
                               profile_spec=None,
                               hist_spec=None) -> "dict[str, int]":
    """Itemized PER-DEVICE residency of one mesh cell under the round-18
    2D batch x tile campaign layout: each device holds
    `sims_per_shard` sims' tile blocks.

    The split follows the shard_map sharding policy
    (parallel/mesh._SHARD_MAP_LOCAL): the big per-tile arrays, the
    trace rows and the per-tile profile ring hold 1/tile_shards of
    their tile axis per device; the replicated control state and the
    telemetry ring (scalar series, identical on every tile shard) are
    held in full.  `tile_shards=1, sims_per_shard=B` reduces to the
    whole-campaign bill, so one arithmetic serves solo, 1D and 2D
    admission.  `state_split` (a precomputed
    `parallel/mesh.shard_split_bytes` dict) substitutes for `state`
    when the caller dropped the probe pytree and kept only the byte
    counts (the admission controller's JobMeasure).  Returns consumer
    -> bytes plus a "total" key — the same shape
    `residency_breakdown` produces, so `format_breakdown` and the
    refusal messages serve both."""
    sims = int(sims_per_shard)
    dt = max(int(tile_shards), 1)
    out: "dict[str, int]" = {}
    if state is not None and state_split is None:
        from graphite_tpu.parallel.mesh import shard_split_bytes

        state_split = shard_split_bytes(state)
    if state_split is not None:
        out["state"] = sims * (int(state_split["replicated"])
                               + int(state_split["tile_local"]) // dt)
    if per_sim_trace_bytes:
        out["trace"] = sims * (int(per_sim_trace_bytes) // dt)
    if telemetry_spec is not None:
        out["telemetry"] = sims * int(telemetry_ring_bytes(telemetry_spec))
    if profile_spec is not None:
        out["profile"] = sims * int(profile_spec.ring_bytes(
            tile_shards=dt))
    if hist_spec is not None:
        # the aggregate [H, B] ring is replicated (held in full per
        # shard); only a per-tile [T, H, B] ring splits its tile axis
        out["hist"] = sims * int(hist_spec.ring_bytes(
            tile_shards=dt if hist_spec.per_tile else 1))
    out["total"] = sum(out.values())
    return out


def telemetry_ring_bytes(spec) -> int:
    """Per-sim bytes of a telemetry spec's device-resident state (ring +
    prev snapshot + cursors) — delegates to the spec's own accounting
    (obs.TelemetrySpec.ring_bytes) so the ONE size model feeds both the
    residency budget and the refusal messages."""
    return int(spec.ring_bytes())


def profile_ring_bytes(spec) -> int:
    """Per-sim bytes of a per-tile profile spec's device-resident state
    (the [S, T, m] ring + prev snapshot + times + cursors) — delegates
    to obs.ProfileSpec.ring_bytes, the ONE size model the admission
    bill and the refusal messages share."""
    return int(spec.ring_bytes())


def hist_ring_bytes(spec) -> int:
    """Per-sim bytes of a latency-histogram spec's device-resident state
    (the int64 bucket-count ring + boundary counter + optional energy
    snapshot) — delegates to obs.HistSpec.ring_bytes, the ONE size
    model the admission bill and the refusal messages share."""
    return int(spec.ring_bytes())


def format_breakdown(breakdown: "dict[str, int]") -> str:
    """One-line human rendering: 'state 1.2 GB + trace 64.0 MB + ...'."""
    parts = [f"{k} {_human(v)}" for k, v in breakdown.items()
             if k != "total"]
    return " + ".join(parts) + f" = {_human(breakdown['total'])}"


def _human(n: int) -> str:
    n = int(n)
    for unit, div in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n} B"


# ---------------------------------------------------------------------------
# per-equation cost walk
# ---------------------------------------------------------------------------

# Shape-only bookkeeping XLA folds into neighbors — excluded from the
# kernel-count proxy (they still contribute bytes when they materialize,
# but counting them as kernels would drown the dispatchable-op signal).
_FREE_PRIMITIVES = frozenset({
    "reshape", "squeeze", "expand_dims", "broadcast_in_dim",
    "convert_element_type", "stop_gradient", "copy",
})

# Call-like primitives whose sub-jaxpr cost IS the eqn's cost (counting
# the call itself would double-count the body).
_CALL_PRIMITIVES = frozenset({"cond", "while", "scan"}) | _DIRECT_CALLS


def _eqn_bytes(eqn) -> "tuple[int, int]":
    """(operand bytes, result bytes) of one equation."""
    in_b = sum(aval_bytes(v.aval) for v in eqn.invars
               if not isinstance(v, Literal))
    out_b = sum(aval_bytes(v.aval) for v in eqn.outvars)
    return in_b, out_b


@dataclasses.dataclass
class DynCost:
    """Trip-weighted cost of executing a jaxpr once: `eqns` counts
    non-free equations (the kernel proxy), `bytes_moved` sums operand +
    result bytes, both with scan lengths multiplied in and cond branches
    resolved to their heaviest arm (the dense-iteration view: every
    phase live is exactly the config-5 floor regime)."""

    eqns: int = 0
    bytes_moved: int = 0

    def __iadd__(self, other: "DynCost"):
        self.eqns += other.eqns
        self.bytes_moved += other.bytes_moved
        return self

    def scaled(self, k: int) -> "DynCost":
        return DynCost(self.eqns * k, self.bytes_moved * k)


def dynamic_cost(jaxpr, *, while_trips: int = 1) -> DynCost:
    """Trip-weighted execution cost of `jaxpr` (see DynCost).

    scan multiplies its body by the static `length`; while bodies count
    `while_trips` times (default 1 — the per-iteration view); cond costs
    its heaviest branch (one branch executes; the heavy one is the dense
    floor).  The eqn count is a KERNEL PROXY: XLA fuses, so real kernel
    counts are lower, but fusion is local and stable — the proxy moves
    with the program.
    """
    total = DynCost()
    j = as_jaxpr(jaxpr)
    for eqn in j.eqns:
        name = eqn.primitive.name
        in_b, out_b = _eqn_bytes(eqn)
        if name == "cond":
            branch_costs = [
                dynamic_cost(b, while_trips=while_trips)
                for _, b in subjaxprs(eqn)
            ]
            if branch_costs:
                total += max(branch_costs, key=lambda c: c.bytes_moved)
            # the select/copy of the carried outputs is real traffic
            total += DynCost(0, out_b)
            continue
        if name in _CALL_PRIMITIVES or list(subjaxprs(eqn)):
            mult = 1
            if name == "scan":
                mult = int(eqn.params.get("length", 1))
            elif name == "while":
                mult = int(while_trips)
            inner = DynCost()
            for _, sub in subjaxprs(eqn):
                inner += dynamic_cost(sub, while_trips=while_trips)
            total += inner.scaled(mult)
            continue
        total += DynCost(0 if name in _FREE_PRIMITIVES else 1,
                         in_b + out_b)
    return total


# ---------------------------------------------------------------------------
# peak-live residency scan
# ---------------------------------------------------------------------------


def peak_live_bytes(jaxpr, _memo=None) -> int:
    """Static peak-live-bytes estimate of executing `jaxpr` once.

    Linear live-range scan: the program's consts + invars are live at
    entry; each eqn's outputs materialize ON TOP of everything still
    live (so a cond/while whose outputs mirror its carried operands
    models XLA's double-buffering of branch/loop outputs — the round-6
    contract's cost); a var dies after its last use.  Call-like eqns add
    their sub-jaxpr's own transient peak (minus the operand bytes
    already counted as live here).  No buffer donation, aliasing, or
    fusion — a deliberate over-estimate whose deviation from the
    backend's `memory_analysis()` is recorded, not hidden.
    """
    if _memo is None:
        _memo = {}
    j = as_jaxpr(jaxpr)
    if id(j) in _memo:
        return _memo[id(j)]

    outset = {v for v in j.outvars
              if not isinstance(v, Literal)}
    last: dict = {}
    for i, eqn in enumerate(j.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last[v] = i

    live: dict = {}
    for v in list(j.constvars) + list(j.invars):
        live[v] = aval_bytes(v.aval)
    live_b = sum(live.values())
    peak = live_b
    # inputs nothing consumes (and that aren't outputs) die at entry
    for v in list(live):
        if v not in last and v not in outset:
            live_b -= live.pop(v)

    for i, eqn in enumerate(j.eqns):
        out_b = sum(aval_bytes(v.aval) for v in eqn.outvars)
        inner_extra = 0
        for _, sub in subjaxprs(eqn):
            sj = as_jaxpr(sub)
            sub_in = sum(aval_bytes(v.aval)
                         for v in list(sj.constvars) + list(sj.invars))
            inner_extra = max(inner_extra,
                              peak_live_bytes(sj, _memo) - sub_in)
        peak = max(peak, live_b + out_b + inner_extra)
        for v in eqn.outvars:
            if v in live:
                continue
            b = aval_bytes(v.aval)
            live[v] = b
            live_b += b
        for v in list(live):
            if last.get(v, -1) <= i and v not in outset:
                live_b -= live.pop(v)

    _memo[id(j)] = peak
    return peak


# ---------------------------------------------------------------------------
# per-iteration / per-phase attribution
# ---------------------------------------------------------------------------


def main_loop_body(jaxpr):
    """The body jaxpr of the program's main loop — the `while` eqn with
    the most nested equations (the quantum loop in `run_simulation`, the
    bounded dispatch loop under barrier_host).  None when the program
    has no while loop (single-quantum regions)."""
    best, best_n = None, -1
    for _, eqn in iter_eqns_with_site(jaxpr):
        if eqn.primitive.name != "while":
            continue
        body = as_jaxpr(eqn.params["body_jaxpr"])
        n = sum(1 for _ in iter_eqns(body))
        if n > best_n:
            best, best_n = body, n
    return best


@dataclasses.dataclass
class PhaseCost:
    """One protocol phase's share of the per-iteration cost (the cost of
    its gating cond's heaviest branch)."""

    name: str
    eqns: int
    bytes_moved: int

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def per_phase_costs(body, n_tiles: int,
                    phase_names=()) -> "list[PhaseCost]":
    """Attribute the per-iteration kernel proxy to protocol phases via
    the round-6 phase-cond structure (rules.phase_conds finds the conds
    that output mailbox matrices).  Conds appear in program order ==
    phase order; unnamed extras (or an ungated program's zero conds)
    degrade gracefully."""
    from graphite_tpu.analysis.rules import phase_conds

    out = []
    for k, (site, eqn) in enumerate(phase_conds(body, n_tiles)):
        branch_costs = [dynamic_cost(b) for _, b in subjaxprs(eqn)]
        heavy = max(branch_costs, key=lambda c: c.bytes_moved) \
            if branch_costs else DynCost()
        name = (phase_names[k] if k < len(phase_names)
                else f"phase_{k}")
        out.append(PhaseCost(name, heavy.eqns, heavy.bytes_moved))
    return out


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

_TOP_EQNS = 5


@dataclasses.dataclass
class CostReport:
    """One program's static cost & residency measurements.

    `metrics()` is the budgeted subset; the rest is context the CLI
    emits for humans (per-phase table, top-contributing equations, the
    backend memory_analysis comparison when one was recorded)."""

    program: str
    tiles: int                 # geometry the program was lowered at
    n_eqns_total: int          # every eqn at every depth, once
    kernels_per_iter: int      # trip-weighted proxy inside the main loop
    bytes_per_iter: int        # trip-weighted operand+result bytes there
    arg_bytes: int             # program inputs (consts + invars)
    out_bytes: int             # program outputs
    peak_bytes: int            # live-range scan peak (over-estimate)
    phase_costs: "list[PhaseCost]" = dataclasses.field(
        default_factory=list)
    base_kernels_per_iter: int = 0  # per-iter eqns outside the phase conds
    top_eqns: "list[dict]" = dataclasses.field(default_factory=list)
    memory_cmp: "dict | None" = None  # backend_memory_comparison output
    # round 22: the static collective/ICI metrics (analysis/comms.py).
    # None on non-mesh programs — the keys exist only where collectives
    # can, so every pre-round-22 BUDGETS.json entry stays byte-identical
    collectives_per_iter: "int | None" = None
    ici_bytes_per_iter: "int | None" = None

    def metrics(self) -> "dict[str, int]":
        out = {m: int(getattr(self, m)) for m in BUDGET_METRICS}
        for m in COMMS_METRICS:
            v = getattr(self, m)
            if v is not None:
                out[m] = int(v)
        return out

    def to_json(self) -> dict:
        return {
            "cost": True,
            "program": self.program,
            "tiles": self.tiles,
            **self.metrics(),
            "base_kernels_per_iter": self.base_kernels_per_iter,
            "phases": [p.to_json() for p in self.phase_costs],
            "top_eqns": self.top_eqns,
            **({"memory_analysis": self.memory_cmp}
               if self.memory_cmp is not None else {}),
        }


def _top_eqns(jaxpr, k: int = _TOP_EQNS) -> "list[dict]":
    """The k largest equations by result bytes — the named suspects a
    budget-gate failure points at."""
    rows = []
    for site, eqn in iter_eqns_with_site(jaxpr):
        if eqn.primitive.name in _CALL_PRIMITIVES:
            continue  # a call's bytes are its body's; name leaves
        in_b, out_b = _eqn_bytes(eqn)
        if out_b == 0:
            continue
        shape = getattr(eqn.outvars[0].aval, "shape", ())
        dtype = str(getattr(eqn.outvars[0].aval, "dtype", "?"))
        rows.append({"site": site, "primitive": eqn.primitive.name,
                     "out_bytes": int(out_b), "in_bytes": int(in_b),
                     "shape": [int(d) for d in shape], "dtype": dtype})
    rows.sort(key=lambda r: r["out_bytes"], reverse=True)
    return rows[:k]


def cost_report(spec) -> CostReport:
    """Measure one audited program (an audit.ProgramSpec)."""
    closed = spec.closed
    j = as_jaxpr(closed)
    arg_b = sum(aval_bytes(v.aval)
                for v in list(j.constvars) + list(j.invars))
    out_b = sum(aval_bytes(v.aval) for v in j.outvars
                if not isinstance(v, Literal))
    n_total = sum(1 for _ in iter_eqns(closed))
    body = main_loop_body(closed)
    if body is not None:
        it = dynamic_cost(body)
        phases = per_phase_costs(body, spec.n_tiles,
                                 getattr(spec, "phase_names", ()))
    else:
        it = dynamic_cost(closed)
        phases = per_phase_costs(closed, spec.n_tiles,
                                 getattr(spec, "phase_names", ()))
    # lazy: comms imports this module (main_loop_body) at its top
    from graphite_tpu.analysis import comms

    cm = comms.collective_metrics(spec)
    return CostReport(
        program=spec.name,
        tiles=int(spec.n_tiles),
        n_eqns_total=n_total,
        kernels_per_iter=it.eqns,
        bytes_per_iter=it.bytes_moved,
        arg_bytes=arg_b,
        out_bytes=out_b,
        peak_bytes=peak_live_bytes(closed),
        phase_costs=phases,
        base_kernels_per_iter=it.eqns - sum(p.eqns for p in phases),
        top_eqns=_top_eqns(closed),
        collectives_per_iter=(None if cm is None
                              else cm["collectives_per_iter"]),
        ici_bytes_per_iter=(None if cm is None
                            else cm["ici_bytes_per_iter"]),
    )


# ---------------------------------------------------------------------------
# backend cross-check: compiled.memory_analysis()
# ---------------------------------------------------------------------------

# Documented agreement tolerance of the static model vs the backend's
# own accounting, where the backend provides memory_analysis():
#  - arguments/outputs: within ARG_OUT_TOL (layout padding only);
#  - peak: within [1, PEAK_OVER_FACTOR] x the backend's argument +
#    output + temp total (the live-range scan ignores donation/aliasing
#    and in-place loop-carry updates, so it over-estimates; it must
#    never UNDER-estimate the backend's floor).
ARG_OUT_TOL = 0.10
PEAK_OVER_FACTOR = 8.0


def backend_memory_comparison(fn, args, report: "CostReport | None" = None,
                              ) -> "dict | None":
    """Compile `fn(*args)` on the current backend and compare its
    `memory_analysis()` against the static estimate.  Returns None when
    the backend provides no analysis.  This COMPILES (the one cost.py
    operation that does) — callers gate it behind tests/flags."""
    compiled = jax.jit(fn).lower(*args).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    out = {
        "backend": jax.default_backend(),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    if report is not None:
        total = (out["argument_bytes"] + out["output_bytes"]
                 + out["temp_bytes"])
        out["static_arg_bytes"] = report.arg_bytes
        out["static_out_bytes"] = report.out_bytes
        out["static_peak_bytes"] = report.peak_bytes
        if total:
            out["peak_over_backend"] = round(report.peak_bytes / total, 3)
        report.memory_cmp = out
    return out


# ---------------------------------------------------------------------------
# budget layer
# ---------------------------------------------------------------------------

BUDGET_METRICS = ("n_eqns_total", "kernels_per_iter", "bytes_per_iter",
                  "arg_bytes", "out_bytes", "peak_bytes")

# round 22: the collective/ICI pair, budgeted ONLY on mesh programs
# (CostReport carries None elsewhere and metrics() drops them — the
# keys never appear in a non-mesh BUDGETS.json entry).  The ratchet
# over ici_bytes_per_iter is the [T, k] mailbox compaction's
# acceptance metric (ROADMAP).
COMMS_METRICS = ("collectives_per_iter", "ici_bytes_per_iter")

# ceiling = measured * rel + abs: counts get 10% + a small absolute
# slack (jax point releases shuffle a few eqns), byte metrics 15% + 64 KB
# (padding/layout noise) — tight enough that a doubled carried buffer or
# a new per-iteration phase trips, loose enough that benign refactors
# don't cry wolf.
_SLACK = {
    "n_eqns_total": (1.10, 16),
    "kernels_per_iter": (1.10, 8),
    "bytes_per_iter": (1.15, 1 << 16),
    "arg_bytes": (1.05, 1 << 12),
    "out_bytes": (1.05, 1 << 12),
    "peak_bytes": (1.15, 1 << 16),
    # collective counts are exact program structure — a single stray
    # collective should blow the count budget, so the absolute slack is
    # small; ICI bytes get byte-metric treatment at a 4 KB floor (the
    # audited shapes move only a few KB per iteration)
    "collectives_per_iter": (1.10, 2),
    "ici_bytes_per_iter": (1.15, 1 << 12),
}


def default_budgets_path() -> str:
    """BUDGETS.json at the repo root (next to BASELINE.json)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)),
                        "BUDGETS.json")


def ceilings(report: CostReport) -> "dict[str, int]":
    return {m: int(v * _SLACK[m][0]) + _SLACK[m][1]
            for m, v in report.metrics().items()}


class BudgetRatchetError(ValueError):
    """A ratcheted budget refresh tried to RAISE a ceiling.

    `save_budgets(..., ratchet=True)` only lowers ceilings: a perf PR's
    win is locked in, and a later refresh cannot silently absorb a
    regression by re-baselining above the old ceiling.  Raising a
    metric requires naming it explicitly (`allow_increase` /
    `--allow-increase <metric>`), which makes the increase a reviewed
    decision instead of a side effect.  The message lists every
    offending (program, metric, old ceiling, new ceiling) tuple."""


def save_budgets(reports: "list[CostReport]", path: "str | None" = None,
                 fingerprints: "dict[str, str] | None" = None,
                 registry: "dict | None" = None, *,
                 ratchet: bool = False,
                 allow_increase: "tuple[str, ...]" = ()) -> str:
    """Write measured baselines + slack ceilings for `reports` (the
    --budget-update refresh; merges over an existing file so a subset
    run never drops the other programs' entries).  `fingerprints` maps
    program name -> identity digest (analysis/identity.fingerprint):
    each entry records WHICH program its ceilings were measured at, so
    the gate can refuse stale ceilings after an identity change.
    `registry` (name -> registry.ProgramRecord) keys each entry under
    the program's registered `budget_key` — the SAME key check_budget
    reads, so a refresh after a rename replaces the entry the gate
    resolves instead of orphaning a new-name copy next to the stale
    old-key one.

    `ratchet=True` (round 12): the refresh may only LOWER ceilings.  A
    metric whose new ceiling would exceed the existing entry's raises
    `BudgetRatchetError` unless it is named in `allow_increase` — the
    post-perf-PR refresh mode that locks wins in."""
    path = path or default_budgets_path()
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    offenders = []
    for rep in reports:
        entry = {
            "tiles": int(rep.tiles),
            "measured": rep.metrics(),
            "ceiling": ceilings(rep),
        }
        if fingerprints and rep.program in fingerprints:
            entry["fingerprint"] = fingerprints[rep.program]
        rec = registry.get(rep.program) if registry else None
        key = rec.budget_key if rec is not None else rep.program
        if ratchet and key in data:
            old_ceil = data[key].get("ceiling", {})
            for m, c in entry["ceiling"].items():
                old = old_ceil.get(m)
                if old is None or c <= int(old):
                    continue
                if m in allow_increase:
                    continue
                offenders.append((rep.program, m, int(old), int(c)))
        data[key] = entry
    if offenders:
        rows = "; ".join(
            f"{prog}.{m}: ceiling {old} -> {new}"
            for prog, m, old, new in offenders)
        raise BudgetRatchetError(
            f"ratcheted refresh would RAISE {len(offenders)} ceiling(s): "
            f"{rows} — pass --allow-increase <metric> for each metric "
            f"whose increase is an intentional, reviewed decision")
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def load_budgets(path: "str | None" = None) -> dict:
    path = path or default_budgets_path()
    with open(path) as f:
        return json.load(f)


def check_budget(report: CostReport, budgets: dict,
                 record=None) -> list:
    """Gate one report against the checked-in budgets.  Returns
    rules.Finding rows (rule "budget", error severity) — empty means
    within budget.  A missing program entry is itself an error: silence
    on a new program would let it grow unbudgeted.

    `record` (a registry.ProgramRecord) resolves the program THROUGH
    the registry: the budget entry is looked up under the record's
    `budget_key` (renames keep their ceilings reachable), and an entry
    whose recorded fingerprint no longer matches the REGISTERED
    program's is a loud error — a retraced program can no longer
    silently inherit ceilings measured on a different artifact."""
    from graphite_tpu.analysis.rules import Finding, SEV_ERROR

    key = record.budget_key if record is not None else report.program
    entry = budgets.get(key)
    if entry is None:
        return [Finding(
            "budget", SEV_ERROR, "BUDGETS.json",
            f"no budget entry for program {report.program!r} "
            + (f"(registry key {key!r}) " if key != report.program
               else "")
            + f"— run `python -m graphite_tpu.tools.audit "
            f"--budget-update` after reviewing its cost report",
            program=report.program,
            data={"metrics": report.metrics()})]
    if record is not None and entry.get("fingerprint") is None:
        # a fingerprint-less entry resolved through the registry cannot
        # be staleness-checked — silence here would reopen the exact
        # stale-ceilings gap the identity plumbing closes
        return [Finding(
            "budget", SEV_ERROR, "BUDGETS.json",
            f"budget entry {key!r} records no fingerprint (it predates "
            f"the program registry) so its ceilings cannot be checked "
            f"against the registered artifact — refresh with "
            f"--budget-update",
            program=report.program,
            data={"registered_fingerprint": record.fingerprint})]
    if record is not None \
            and entry["fingerprint"] != record.fingerprint:
        return [Finding(
            "budget", SEV_ERROR, "BUDGETS.json",
            f"budget entry {key!r} was measured at fingerprint "
            f"{entry['fingerprint'][:24]}... but the registered "
            f"program is {record.fingerprint[:24]}... — the ceilings "
            f"are STALE for this artifact; review the cost report and "
            f"refresh with --budget-update (after --lock-update)",
            program=report.program,
            data={"budget_fingerprint": entry["fingerprint"],
                  "registered_fingerprint": record.fingerprint})]
    base_tiles = entry.get("tiles")
    if base_tiles is not None and report.tiles \
            and int(base_tiles) != int(report.tiles):
        # eqn counts and footprints scale with geometry: gating a
        # 16-tile lowering against 8-tile ceilings fabricates
        # regressions, and a mismatched --budget-update would silently
        # defang the default-geometry CI gate
        return [Finding(
            "budget", SEV_ERROR, "BUDGETS.json",
            f"program {report.program!r} was lowered at tiles="
            f"{report.tiles} but its budget entry was measured at "
            f"tiles={base_tiles} — rerun at the budgeted geometry, or "
            f"refresh with --budget-update at the new one",
            program=report.program,
            data={"tiles": int(report.tiles),
                  "budget_tiles": int(base_tiles)})]
    out = []
    ceil = entry["ceiling"]
    for m, v in report.metrics().items():
        c = ceil.get(m)
        if c is None:
            # a metric with no ceiling would grow unbudgeted — same
            # failure mode as a missing program entry, same severity
            out.append(Finding(
                "budget", SEV_ERROR, "BUDGETS.json",
                f"no ceiling for metric {m!r} of program "
                f"{report.program!r} (stale BUDGETS.json?) — refresh "
                f"with --budget-update", program=report.program,
                data={"metric": m, "measured": int(v)}))
            continue
        if v <= c:
            continue
        suspect = report.top_eqns[0] if report.top_eqns else None
        extra = ""
        if suspect and m in ("bytes_per_iter", "peak_bytes", "arg_bytes",
                             "out_bytes"):
            extra = (f"; largest equation: {suspect['primitive']} "
                     f"{suspect['shape']} {suspect['dtype']} "
                     f"({_human(suspect['out_bytes'])}) at "
                     f"{suspect['site']}")
        out.append(Finding(
            "budget", SEV_ERROR, "BUDGETS.json",
            f"{m} = {v} exceeds the budget ceiling {c} "
            f"(baseline {entry['measured'].get(m)}){extra} — if the "
            f"change is intentional, refresh with --budget-update",
            program=report.program,
            data={"metric": m, "measured": int(v), "ceiling": int(c),
                  "baseline": entry["measured"].get(m),
                  **({"suspect": suspect} if suspect else {})}))
    return out


def check_budgets(reports: "list[CostReport]", budgets: dict,
                  registry: "dict | None" = None) -> list:
    """Gate every report; `registry` (name -> registry.ProgramRecord,
    from registry.load_lock) resolves budget keys and arms the
    stale-fingerprint check per report."""
    out = []
    for rep in reports:
        rec = registry.get(rep.program) if registry else None
        out.extend(check_budget(rep, budgets, record=rec))
    return out


# ---------------------------------------------------------------------------
# known-regression fixture
# ---------------------------------------------------------------------------


def budget_regression_fixture(tiles: int = 8, pad_mb: int = 96):
    """The gated-MSI program with an artificially inflated carried
    buffer — the known-regression fixture the budget gate must trip on
    (naming the offending equation).  Wraps the REAL audited program:
    an extra `pad_mb` int64 buffer rides a while carry alongside it,
    exactly the shape of regression the gate exists for (a layout
    mistake ballooning a loop-carried temp — round 4's 10.7 GB lesson).
    Returns an audit.ProgramSpec named "gated-msi" so the check runs
    against the real program's checked-in ceilings."""
    import jax.numpy as jnp

    from graphite_tpu.analysis.audit import default_programs, \
        spec_from_simulator  # noqa: F401  (spec type)

    spec = default_programs(tiles, names=("gated-msi",))[0]
    closed = spec.closed

    n_pad = (pad_mb << 20) // 8

    def inflated(pad, *args):
        out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)

        def body(c):
            p, i = c
            return p + i, i + 1

        pad2, _ = jax.lax.while_loop(
            lambda c: c[1] < 4, body, (pad, jnp.asarray(0, jnp.int64)))
        return tuple(out) + (pad2,)

    pad_abs = jax.ShapeDtypeStruct((n_pad,), jnp.int64)
    in_abs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
              for v in closed.jaxpr.invars]
    inflated_closed = jax.make_jaxpr(inflated)(pad_abs, *in_abs)
    return dataclasses.replace(
        spec, closed=inflated_closed,
        invar_paths=["pad"] + list(spec.invar_paths),
        # the pad invar shifts every original invar one slot right
        clock_invars=tuple(i + 1 for i in spec.clock_invars))
