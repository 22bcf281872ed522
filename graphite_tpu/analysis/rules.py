"""Jaxpr invariant lints: the rules the program auditor runs.

Each rule checks one property Graphite's performance story depends on,
on the LOWERED program (a ClosedJaxpr from `jax.make_jaxpr`) — the
artifact the compiler actually sees, so a regression cannot hide behind
a Python-level abstraction:

  cond-payload  no lax.cond output may carry a big store (round 6: the
                directory entry/sharers must ride `_DirAcc`/`_RowAcc`
                delta plans, because XLA double-buffers cond outputs)
  knob-fold     every sweep timing knob must be CONSUMED as a traced
                operand (round 7: a knob the engine reads off static
                params instead constant-folds — one recompile per grid
                point and a silently wrong sweep report)
  time-dtype    no integer narrowing of values derived from absolute
                picosecond clocks (time_types.TIME_DTYPE discipline;
                deltas/latencies are legitimately int32)
  vmap-gate     a program built with phase_gate=True whose gating conds
                lowered to both-branch selects (vmap batching) is paying
                gating's bookkeeping and buying nothing (round-7 PERF
                finding — SweepRunner maps its sims under a named axis
                the predicates are reduced over, so its conds survive)
  host-sync     no callback/infeed/outfeed/debug_print primitive inside
                the compiled step (a host round trip per iteration —
                the whole reason the quantum loop is device-driven;
                its cost is not measured on the current machine)
  scatter-determinism
                inside a vmapped campaign (or any shard_mapped region)
                a replace-combiner scatter whose index rows can alias
                has an implementation-defined winner — the round-9
                telemetry contract says device stores are masked
                add-scatters; this enforces it program-wide.  A scatter
                passes by being commutative (add/mul/min/max), by
                declaring unique_indices, by an index-provenance proof
                (an iota column survives into every row — walk.
                distinct_axes), or by the masked scratch-redirect idiom
                (disabled lanes select a constant spill slot)
  telemetry-off a program lowered with telemetry=None must contain NO
                trace of the timeline machinery: no telemetry-state
                invar and no equation producing the ring's
                [S, n_series] aval (round 9's knobs=None-style
                contract — the default program stays bit-identical to
                the pre-telemetry one).  Telemetry-ON programs instead
                add the ring's aval to the cond-payload forbidden set:
                no phase cond may ever carry the buffer.
  profile-off   the same rule over the round-16 spatial profiler
                (telemetry_off with state_key="profile"): a
                profile=None program carries no profile-state invar and
                no [S, T, m] per-tile ring equation; profile-ON
                programs add that ring's aval to the cond-payload
                forbidden set instead.
  hist-off      the same rule over the round-21 latency histograms
                (telemetry_off with state_key="hist"): a hist=None
                program carries no hist-state invar and no int64
                [H, B] / [T, H, B] bucket-count ring equation; hist-ON
                programs add that ring's aval to the cond-payload
                forbidden set instead.
  write-race    the round-20 [T, k]-compaction gate: every scatter is
                classified single-writer / commutative-multi-writer /
                ordered-multi-writer through the shared writer-proof
                ladder (walk.scatter_writer_proof); an ORDERED write
                into a req lane (uint8/int64 [.., T]) or a mailbox
                matrix ([.., T, T]) is an error — a rewrite silently
                made a deterministic protocol lane racy.  The model
                checker (analysis/protocol.py) supplies the reachable
                per-matrix fan-in bounds the compaction needs;
                `lane_writes`/`lane_summary` expose the classification
                table (`tools/audit.py --lanes`).

Rules return `Finding` lists; `analysis/audit.py` assembles them into
per-program reports and the `tools/audit.py` CLI emits them as JSON
lines.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

from graphite_tpu.analysis.walk import (
    aval_bytes, aval_sig, call_arg_maps, is_platform_choice,
    iter_eqns_with_site,
    make_scope, scatter_writer_proof, scope_from_closed, subjaxprs,
    taint_narrowing, used_invar_mask,
)

SEV_ERROR = "error"
SEV_WARNING = "warning"


@dataclasses.dataclass
class Finding:
    """One rule violation at one program site."""

    rule: str
    severity: str          # SEV_ERROR | SEV_WARNING
    site: str              # primitive path, e.g. "while/body.cond"
    message: str
    program: "str | None" = None   # filled in by audit()
    data: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> dict:
        out = {"rule": self.rule, "severity": self.severity,
               "site": self.site, "message": self.message}
        if self.program is not None:
            out["program"] = self.program
        if self.data:
            out["data"] = self.data
        return out

    def __str__(self) -> str:
        prog = f"{self.program}: " if self.program else ""
        return f"[{self.rule}/{self.severity}] {prog}{self.message} " \
               f"(at {self.site})"


def _sig_matches(sig, forbidden_sig) -> bool:
    """Aval signature match, ignoring leading batch axes: a vmapped
    program carries the same store as [B, *shape]."""
    if sig is None:
        return False
    shape, dtype = sig
    fshape, fdtype = forbidden_sig
    if dtype != fdtype or len(shape) < len(fshape):
        return False
    return tuple(shape[len(shape) - len(fshape):]) == tuple(fshape)


# ---------------------------------------------------------------------------
# rule 1: cond-payload
# ---------------------------------------------------------------------------


def cond_payload(jaxpr, *, max_bytes: "int | None" = None,
                 forbidden=()) -> "list[Finding]":
    """No lax.cond output may exceed `max_bytes` or match a `forbidden`
    (shape, dtype) signature (the directory stores).

    XLA double-buffers cond branch outputs, so a big array riding a cond
    costs a full extra copy in HBM every iteration — the round-2
    pathology that round 6's `_DirAcc`/`_RowAcc` delta plans exist to
    avoid.  Checked for EVERY cond at EVERY nesting depth, not just the
    one a test happens to sample — but for the choice of a lowering
    platform (`walk.is_platform_choice`: resolved when the program is
    lowered, so no branch output exists to double-buffer; the shared-L2
    landing picks its TPU kernel that way, on the row-flat sharers
    store).  Its branches are walked like any other sub-jaxpr.
    """
    forbidden = tuple((tuple(s), str(np.dtype(d))) for s, d in forbidden)
    out = []
    for site, eqn in iter_eqns_with_site(jaxpr):
        if eqn.primitive.name != "cond" or is_platform_choice(eqn):
            continue
        for k, v in enumerate(eqn.outvars):
            sig = aval_sig(v.aval)
            for fsig in forbidden:
                if _sig_matches(sig, fsig):
                    out.append(Finding(
                        "cond-payload", SEV_ERROR, site,
                        f"lax.cond output {k} carries a forbidden store "
                        f"{sig[0]} {sig[1]} — it will be double-buffered "
                        f"(round-6 _DirAcc/_RowAcc contract)",
                        data={"output": k, "shape": list(sig[0]),
                              "dtype": sig[1],
                              "bytes": aval_bytes(v.aval)}))
                    break
            else:
                b = aval_bytes(v.aval)
                if max_bytes is not None and b > max_bytes:
                    sig = sig or ((), "?")
                    out.append(Finding(
                        "cond-payload", SEV_ERROR, site,
                        f"lax.cond output {k} is {b} bytes "
                        f"({sig[0]} {sig[1]}) > max_cond_bytes="
                        f"{max_bytes} — cond outputs are double-buffered",
                        data={"output": k, "bytes": b,
                              "shape": list(sig[0]), "dtype": sig[1]}))
    return out


# ---------------------------------------------------------------------------
# rule 2: knob-fold
# ---------------------------------------------------------------------------


def knob_fold(jaxpr, knob_invars: "dict[str, list[int]]",
              invar_paths=None) -> "list[Finding]":
    """Every sweep knob's invar must be transitively consumed by the
    lowered program.

    A knob leaf that reaches the jit as an argument but feeds no eqn
    means the engine read the STATIC param instead — the value is
    constant-folded, the sweep reports knob points that never entered
    the program, and every grid point recompiles (the round-7 zero-
    recompile contract).
    """
    mask = used_invar_mask(jaxpr)
    out = []
    for name, idxs in sorted(knob_invars.items()):
        if not idxs:
            out.append(Finding(
                "knob-fold", SEV_ERROR, "jaxpr.invars",
                f"knob {name!r} has no traced invar at all — it was "
                f"baked into the program as a literal",
                data={"knob": name}))
            continue
        if not any(mask[i] for i in idxs if i < len(mask)):
            paths = ([invar_paths[i] for i in idxs]
                     if invar_paths else idxs)
            out.append(Finding(
                "knob-fold", SEV_ERROR, "jaxpr.invars",
                f"knob {name!r} rides as a traced argument but nothing "
                f"consumes it — the engine constant-folded the static "
                f"param value instead (invars {paths})",
                data={"knob": name, "invars": list(idxs)}))
    return out


# ---------------------------------------------------------------------------
# rule 3: time-dtype
# ---------------------------------------------------------------------------


def time_dtype(jaxpr, clock_invars, invar_paths=None) -> "list[Finding]":
    """No integer narrowing of values derived from absolute picosecond
    clocks (the `clock_invars` taint sources — TIME_DTYPE leaves).

    A 1 GHz tile overflows int32 picoseconds after ~2 ms of simulated
    time, so absolute clocks are int64 everywhere (time_types.py).
    Taint stops at subtraction — a difference of clocks is a delta,
    which the engine legitimately keeps in int32 (DELTA_DTYPE).
    """
    j = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    n = len(j.invars)
    in_taint = [False] * n
    for i in clock_invars:
        if i < n:
            in_taint[i] = True
    out = []

    def on_finding(site, eqn, old, new):
        out.append(Finding(
            "time-dtype", SEV_ERROR, site,
            f"value derived from an absolute picosecond clock is "
            f"narrowed {np.dtype(old).name} -> {np.dtype(new).name} "
            f"(TIME_DTYPE discipline: absolute times stay int64; only "
            f"deltas may narrow)",
            data={"from": np.dtype(old).name, "to": np.dtype(new).name}))

    taint_narrowing(jaxpr, in_taint, on_finding)
    return out


# ---------------------------------------------------------------------------
# rule 4: vmap-gate
# ---------------------------------------------------------------------------


def phase_conds(jaxpr, n_tiles: int) -> list:
    """(site, eqn) of every cond that writes a mailbox — the memory
    engines' per-phase gating conds.  Each protocol phase writes either
    a uint8[.., T, T] type matrix (fwd/ack/evict) or, since the round-12
    request compaction, the per-REQUESTER lane signature: a uint8[.., T]
    type vector TOGETHER with an int64[.., T] time vector (the shared-L2
    requester phase's only mailbox write is the compacted request lane).
    Nothing else in the mem_gate-off programs emits either shape set as
    a cond output; see tests/test_phase_gating."""
    out = []
    for site, eqn in iter_eqns_with_site(jaxpr):
        if eqn.primitive.name == "cond" \
                and _mailbox_outputs(eqn, n_tiles):
            out.append((site, eqn))
    return out


def _mailbox_outputs(eqn, n_tiles: int) -> list:
    outs = []
    lane_u8 = []
    lane_i64 = False
    progress = False
    for v in eqn.outvars:
        sig = aval_sig(v.aval)
        if not sig:
            continue
        if len(sig[0]) >= 2 and sig[0][-2:] == (n_tiles, n_tiles) \
                and sig[1] == "uint8":
            outs.append(sig)
        if sig == ((), "int32"):
            # every phase cond returns its progress counter — the
            # discriminator that keeps lane-signature matching from
            # catching e.g. the record-fetch cond (uint8 ops + int64
            # dyn costs, but no scalar progress output)
            progress = True
        if sig[0][-1:] == (n_tiles,) and (len(sig[0]) < 2
                                          or sig[0][-2] != n_tiles):
            if sig[1] == "uint8":
                lane_u8.append(sig)
            elif sig[1] == "int64":
                lane_i64 = True
    if lane_u8 and lane_i64 and progress:
        outs.extend(lane_u8)
    return outs


def vmap_gate(jaxpr, n_tiles: int, expect_gated: bool,
              n_phases: int = 6) -> "list[Finding]":
    """A phase_gate=True program whose gating conds did not survive
    lowering is gating in name only.

    `vmap` batches a cond's predicate, which rewrites the cond into
    both-branch execution + `select_n` — every phase then runs every
    iteration AND pays the select (PERF.md round 7 measured gated-vmap
    ~2.8x slower than ungated-vmap).  The engines therefore reduce each
    predicate over the campaign's named sim axis (`ParallelCtx.any_sim`)
    and SweepRunner maps the sims under that name; a bare `vmap` of a
    gated program still fires this rule.  Warning severity: the program
    is correct, just paying for a mechanism that buys nothing.
    """
    if not expect_gated:
        return []
    conds = phase_conds(jaxpr, n_tiles)
    if len(conds) >= n_phases:
        return []
    n_sel = sum(1 for _, e in iter_eqns_with_site(jaxpr)
                if e.primitive.name == "select_n"
                and _mailbox_outputs(e, n_tiles))
    if not conds:
        return [Finding(
            "vmap-gate", SEV_WARNING, "jaxpr",
            f"program was built with phase_gate=True but NO per-phase "
            f"gating cond survived lowering ({n_sel} mailbox-shaped "
            f"select_n eqns present) — batching turned the gates into "
            f"both-branch selects; map the sims under the named sim "
            f"axis (SweepRunner does), run the batched program ungated, "
            f"or shard the batch axis",
            data={"phase_conds": 0, "mailbox_selects": n_sel})]
    return [Finding(
        "vmap-gate", SEV_WARNING, "jaxpr",
        f"only {len(conds)} of {n_phases} per-phase gating conds "
        f"survived lowering ({n_sel} mailbox-shaped select_n eqns "
        f"present) — part of the engine runs both branches every "
        f"iteration",
        data={"phase_conds": len(conds), "mailbox_selects": n_sel})]


# ---------------------------------------------------------------------------
# rule 5: host-sync
# ---------------------------------------------------------------------------

_HOST_SYNC_NAMES = ("infeed", "outfeed", "debug_print")
_HOST_SYNC_SUBSTR = ("callback",)


def host_sync(jaxpr) -> "list[Finding]":
    """No host round trip inside the compiled step.

    callback/infeed/outfeed/debug_print primitives block the device on
    the host every iteration (a host round trip; its cost is not
    measured on the current machine), which is why the quantum loop is
    device-driven (engine/step.run_simulation) and why `barrier_host`
    batches its dispatches.  A debug print left in an engine phase
    reintroduces exactly that.
    """
    out = []
    for site, eqn in iter_eqns_with_site(jaxpr):
        name = eqn.primitive.name
        if name in _HOST_SYNC_NAMES \
                or any(s in name for s in _HOST_SYNC_SUBSTR):
            out.append(Finding(
                "host-sync", SEV_ERROR, site,
                f"host-synchronizing primitive {name!r} inside the "
                f"compiled step — every iteration would pay a "
                f"host<->device round trip",
                data={"primitive": name}))
    return out


# ---------------------------------------------------------------------------
# rule 6: scatter-determinism
# ---------------------------------------------------------------------------

# Commutative-combiner scatters produce the same result under any
# update order (integer add/mul/min/max are exactly associative), so
# aliasing index rows cannot make them nondeterministic.
_COMMUTATIVE_SCATTERS = frozenset({
    "scatter-add", "scatter-mul", "scatter-min", "scatter-max",
})


def scatter_determinism(jaxpr, *, batched: bool = False,
                        ) -> "list[Finding]":
    """No potentially-aliasing replace-scatter inside a batched region.

    XLA leaves the winner of colliding replace-scatter rows
    implementation-defined; today's serial CPU/TPU lowerings happen to
    pick last-in-index-order, but a parallelized batched lowering is
    free not to — and the repo's bit-identity claims (sweep-vs-
    sequential, telemetry on/off) assume determinism.  `batched=True`
    puts the WHOLE program in scope (it lowers under vmap —
    SweepRunner campaigns); otherwise only `shard_map`ped interiors
    are.  Warning severity, like vmap-gate: the program is correct on
    the backends we run today, but it leans on behavior the contract
    does not own.
    """
    scope0 = scope_from_closed(jaxpr)
    out = []

    def visit(scope, site, in_scope):
        for eqn in scope.jaxpr.eqns:
            name = eqn.primitive.name
            here = f"{site}.{name}" if site else name
            if name.startswith("scatter") and in_scope \
                    and name not in _COMMUTATIVE_SCATTERS:
                # the proof ladder (walk.scatter_writer_proof):
                # unique_indices / constant index rows / a single row
                # per addressed slice / one multi-row axis proven
                # pairwise-distinct by provenance / the masked
                # scratch-redirect idiom.  Sound for at most one
                # multi-row axis — per-axis distinctness covers pairs
                # differing in one axis, not rows differing in several
                # (a const table [[0,1],[1,0]] is distinct along both
                # axes yet rows (0,0) and (1,1) collide)
                if scatter_writer_proof(eqn, scope) is None:
                    idx = eqn.invars[1]
                    sig = aval_sig(eqn.outvars[0].aval) or ((), "?")
                    out.append(Finding(
                        "scatter-determinism", SEV_WARNING, here,
                        f"replace-combiner scatter into {sig[0]} "
                        f"{sig[1]} with potentially aliasing index "
                        f"rows inside a batched region — colliding "
                        f"rows have an implementation-defined "
                        f"winner; use a masked add-scatter (the "
                        f"round-9 ring-store contract), a scratch-"
                        f"slot redirect, or unique_indices=True",
                        data={"shape": list(sig[0]),
                              "dtype": sig[1],
                              "indices_shape": list(
                                  getattr(idx.aval, "shape", ()))}))
            subs = call_arg_maps(eqn)
            if subs:
                tags = [t for t, _ in subjaxprs(eqn)]
                for k, sc in enumerate(subs):
                    tag = tags[k] if k < len(tags) else str(k)
                    visit(make_scope(sc.jaxpr, scope, eqn, sc),
                          f"{here}/{tag}",
                          in_scope or "shard_map" in name)
    visit(scope0, "", batched)
    return out


# ---------------------------------------------------------------------------
# rule 7: telemetry-off
# ---------------------------------------------------------------------------


def telemetry_off(jaxpr, invar_paths=None, ring_sigs=(), *,
                  state_key: str = "telemetry",
                  rule: str = "telemetry-off") -> "list[Finding]":
    """A telemetry=None (or profile=None) program must record nothing.

    Two checks: (a) no invar path names a `state_key` recording-state
    leaf — the None spec must contribute ZERO pytree leaves to the
    carry (the SimState.telemetry=None / SimState.profile=None
    contract), and (b) no equation anywhere in the program produces a
    ring-buffer aval from `ring_sigs` (matched modulo leading batch
    axes, like cond-payload's forbidden set) — a ring materialized
    internally would mean the recording survived constant folding.
    Either finding breaks the round-7-style "None lowers the
    historical program bit-identically" guarantee every overhead claim
    rests on.  The round-16 spatial profiler runs the same rule with
    `state_key="profile"` / `rule="profile-off"` over the [S, T, m]
    ring signatures; the round-21 latency histograms with
    `state_key="hist"` / `rule="hist-off"` over the int64 bucket-count
    ring signatures.
    """
    out = []
    for i, p in enumerate(invar_paths or ()):
        # Match whole path segments, not substrings: state_key="hist"
        # must flag "[0].hist.buf" but NOT the pre-existing counter
        # "[0].mem.counters.line_util_hist".
        if state_key in re.split(r"[.\[\]']+", p):
            out.append(Finding(
                rule, SEV_ERROR, "jaxpr.invars",
                f"{rule} program carries a {state_key}-state "
                f"invar {p!r} (index {i}) — the None spec must add no "
                f"leaves to the carry",
                data={"invar": i, "path": p}))
    ring_sigs = tuple((tuple(s), str(np.dtype(d))) for s, d in ring_sigs)
    if ring_sigs:
        for site, eqn in iter_eqns_with_site(jaxpr):
            for k, v in enumerate(eqn.outvars):
                sig = aval_sig(v.aval)
                for fs in ring_sigs:
                    if _sig_matches(sig, fs):
                        out.append(Finding(
                            rule, SEV_ERROR, site,
                            f"{rule} program contains a "
                            f"ring-store equation "
                            f"({eqn.primitive.name} output {k}, "
                            f"{sig[0]} {sig[1]}) — the recording was "
                            f"not constant-folded away",
                            data={"primitive": eqn.primitive.name,
                                  "output": k, "shape": list(sig[0]),
                                  "dtype": sig[1]}))
                        break
    return out


# ---------------------------------------------------------------------------
# rule 10: write-race
# ---------------------------------------------------------------------------

# Lane kinds, by scatter-target signature (modulo leading batch axes):
#   req-lane  the round-12 compacted per-requester lanes — uint8[.., T]
#             type vectors / int64[.., T] time vectors (one lane per
#             requesting tile; the [T, k] compaction keeps this shape)
#   matrix    the [.., T, T] fwd/ack/evict mailboxes (row per sender or
#             receiver — the multi-writer surface the [T, k] compaction
#             wants to shrink)
#   state     everything else a phase writes: cache tag/state/data
#             arrays, DRAM words, the next-event heap
LANE_REQ = "req-lane"
LANE_MATRIX = "matrix"
LANE_STATE = "state"

CLASS_SINGLE = "single-writer"
CLASS_COMMUTATIVE = "commutative-multi-writer"
CLASS_ORDERED = "ordered-multi-writer"


@dataclasses.dataclass
class LaneWrite:
    """One scatter in the lowered program, classified for the
    write-race lane analysis."""

    site: str            # primitive path of the scatter eqn
    primitive: str       # "scatter", "scatter-add", ...
    kind: str            # LANE_REQ | LANE_MATRIX | LANE_STATE
    classification: str  # CLASS_SINGLE | CLASS_COMMUTATIVE | CLASS_ORDERED
    proof: str           # writer proof name, the combiner, or "-"
    shape: "tuple[int, ...]"
    dtype: str

    def to_json(self) -> dict:
        return {"site": self.site, "primitive": self.primitive,
                "kind": self.kind,
                "classification": self.classification,
                "proof": self.proof, "shape": list(self.shape),
                "dtype": self.dtype}


def _lane_kind(sig, n_tiles: int) -> str:
    shape, dtype = sig
    if len(shape) >= 2 and shape[-2:] == (n_tiles, n_tiles):
        return LANE_MATRIX
    if shape[-1:] == (n_tiles,) \
            and (len(shape) < 2 or shape[-2] != n_tiles) \
            and dtype in ("uint8", "int64"):
        return LANE_REQ
    return LANE_STATE


def lane_writes(jaxpr, n_tiles: int) -> "list[LaneWrite]":
    """Every scatter in the program, classified.

    The ladder: a scatter is SINGLE-WRITER when `walk.
    scatter_writer_proof` proves each target cell is written at most
    once (unique_indices, constant index rows, a single row per
    addressed slice, a provenance-distinct row axis, or the masked
    scratch-redirect); otherwise COMMUTATIVE-MULTI-WRITER when its
    combiner is order-independent (add/mul/min/max); otherwise
    ORDERED-MULTI-WRITER — the result depends on XLA's update order,
    which the contract does not own.  Note the ladder tries the
    single-writer proof even for commutative combiners: the round-12
    req lanes are masked ADD-scatters, and the analysis should say
    "single writer" about them, not merely "commutative"."""
    out = []

    def visit(scope, site):
        for eqn in scope.jaxpr.eqns:
            name = eqn.primitive.name
            here = f"{site}.{name}" if site else name
            if name.startswith("scatter"):
                sig = aval_sig(eqn.outvars[0].aval) or ((), "?")
                proof = scatter_writer_proof(eqn, scope)
                if proof is not None:
                    cls = CLASS_SINGLE
                elif name in _COMMUTATIVE_SCATTERS:
                    cls, proof = CLASS_COMMUTATIVE, name
                else:
                    cls, proof = CLASS_ORDERED, "-"
                out.append(LaneWrite(here, name,
                                     _lane_kind(sig, n_tiles), cls,
                                     proof, tuple(sig[0]), sig[1]))
            subs = call_arg_maps(eqn)
            if subs:
                tags = [t for t, _ in subjaxprs(eqn)]
                for k, sc in enumerate(subs):
                    tag = tags[k] if k < len(tags) else str(k)
                    visit(make_scope(sc.jaxpr, scope, eqn, sc),
                          f"{here}/{tag}")

    visit(scope_from_closed(jaxpr), "")
    return out


def lane_summary(writes: "list[LaneWrite]") -> dict:
    """{kind: {classification: count}} — the lane-classification table
    the README documents and `tools/audit.py --lanes` emits."""
    table = {}
    for w in writes:
        table.setdefault(w.kind, {}) \
             .setdefault(w.classification, 0)
    for w in writes:
        table[w.kind][w.classification] += 1
    return table


def write_race(jaxpr, n_tiles: int, *,
               fan_in: "dict | None" = None) -> "list[Finding]":
    """The standing gate for the [T, k] mailbox compaction.

    Classifies every scatter (`lane_writes`) and fails the audit when a
    rewrite has made a protocol write RACY — an ordered-multi-writer
    scatter into a req lane or a mailbox matrix.  The req lanes are
    single-writer by construction (each tile writes its own lane); the
    matrices are legitimately multi-writer but every current write is
    either provably cell-unique or commutative, and the bit-identity
    claims (sweep-vs-sequential, telemetry on/off, the differential
    model-checker replay) assume exactly that.  A rewrite that turns
    one of these into a replace-scatter with potentially aliasing rows
    silently hands the winner to XLA's update order — this rule is the
    error that stops it.  Ordered writes into other engine state get
    warning severity (scatter-determinism already polices them inside
    batched regions).

    `fan_in`, when given, is the per-matrix reachable fan-in bound from
    the model checker's exhaustive exploration
    (`analysis.protocol.explore(...).fan_in` — e.g. {"req": 1, "fwd":
    1, "ack": 1, "evict": 1}); it is attached to each finding so a
    failure report carries the bound the compaction design needs."""
    out = []
    for w in lane_writes(jaxpr, n_tiles):
        if w.classification != CLASS_ORDERED:
            continue
        gated = w.kind in (LANE_REQ, LANE_MATRIX)
        data = dict(w.to_json())
        if fan_in is not None:
            data["fan_in"] = dict(fan_in)
        if w.kind == LANE_REQ:
            msg = (f"req-lane scatter into {w.shape} {w.dtype} is "
                   f"ordered-multi-writer — the round-12 [T] request "
                   f"lanes are single-writer by construction (each "
                   f"tile owns its lane); this rewrite made the lane "
                   f"racy.  Restore a writer proof: iota/distinct row "
                   f"indices, the masked scratch-redirect, or "
                   f"unique_indices=True")
        elif w.kind == LANE_MATRIX:
            msg = (f"mailbox-matrix scatter into {w.shape} {w.dtype} "
                   f"is ordered-multi-writer — colliding rows hand "
                   f"the winner to XLA's update order and break the "
                   f"bit-identity contract.  Use a commutative "
                   f"combiner (masked add-scatter) or prove the rows "
                   f"distinct")
        else:
            msg = (f"engine-state scatter into {w.shape} {w.dtype} is "
                   f"ordered-multi-writer (no writer proof, "
                   f"non-commutative combiner)")
        out.append(Finding(
            "write-race", SEV_ERROR if gated else SEV_WARNING,
            w.site, msg, data=data))
    return out


# ---------------------------------------------------------------------------
# rule 12: gspmd-insertion (round 22)
# ---------------------------------------------------------------------------


def gspmd_insertion(jaxpr, n_tiles: int, *,
                    phase_names=()) -> "list[Finding]":
    """No collective outside the px packed-exchange whitelist.

    The regression gate for the mesh.py cliff: the packed exchange
    (`ParallelCtx.ag`) emits exactly ONE collective shape — a full-axis
    tiled int64 all_gather of the phase's packed descriptor — and the
    declared replication reductions are full-axis psum-likes.  Anything
    else in a mesh program is a STRAY: the tiny per-field/per-scatter
    collectives the GSPMD partitioner re-inserts when a rewrite loses
    the packing (~270 per iteration, measured 16x slower — see
    parallel/mesh.py's warning block), a partial-axis group reduction,
    or a permute the engine never emits.  Error severity; each finding
    names the collective's protocol phase so the report says WHERE the
    exchange discipline broke."""
    from graphite_tpu.analysis import comms

    out = []
    for c in comms.extract_collectives(
            jaxpr, n_tiles=n_tiles, phase_names=phase_names,
            axis_env=comms.mesh_axis_sizes(jaxpr)):
        if c.kind != comms.KIND_STRAY:
            continue
        out.append(Finding(
            "gspmd-insertion", SEV_ERROR, c.site,
            f"stray collective {c.primitive} over axis "
            f"({c.axis_name}) in phase '{c.phase}': "
            f"{c.dtype}{list(c.shape)} ({c.ici_bytes} ICI bytes) is "
            f"outside the px packed-exchange whitelist (one full-axis "
            f"tiled int64 all_gather per phase) and the declared "
            f"replication reductions — the GSPMD-insertion cliff "
            f"(parallel/mesh.py) reintroduces ~270 such collectives "
            f"per iteration.  Route the field through ParallelCtx.ag's "
            f"packed descriptor instead",
            data=c.to_json()))
    return out


# ---------------------------------------------------------------------------
# rule 13: replication-drift (round 22)
# ---------------------------------------------------------------------------


def replication_drift(jaxpr) -> "list[Finding]":
    """Every shard_map output DECLARED replicated across the tile axis
    must be PROVABLY uniform.

    The multi-chip engine recomputes its [T] control vectors, mailbox
    matrices and sync tables identically on every device
    (parallel/px.py's replication contract; `campaign_state_specs`
    declares them unsharded) — the contract holds only if nothing
    shard-dependent ever reaches a replicated carry slot.  The comms
    analyzer's tile-variance dataflow checks exactly that: variance
    enters at tile-sharded inputs, `axis_index`, and partial-axis
    (grouped) collectives, and is killed only by a full-axis exchange
    or reduction.  A declared-replicated output the dataflow cannot
    prove uniform — e.g. a partial-axis psum leaking a group-local
    value into a replicated carry — is silent cross-device divergence:
    the replicas disagree and every downstream bit-identity claim is
    void.  Error severity; findings name the leaking collective sites."""
    from graphite_tpu.analysis import comms

    out = []
    for row in comms.shard_map_uniformity(jaxpr):
        if not row["non_uniform"]:
            continue
        leak_s = ", ".join(
            f"{lk['primitive']} at {lk['site']}"
            for lk in row["leaks"]) or "no collective leak recorded " \
            "(variance flows from a sharded input or axis_index)"
        out.append(Finding(
            "replication-drift", SEV_ERROR, row["site"],
            f"shard_map output(s) {row['non_uniform']} are declared "
            f"replicated across the tile axis (no tile entry in "
            f"out_specs) but are not provably uniform — a "
            f"shard-dependent value leaks into a replicated carry "
            f"slot and the device replicas can silently diverge.  "
            f"Variance sources: {leak_s}",
            data={"site": row["site"],
                  "non_uniform": list(row["non_uniform"]),
                  "declared_replicated":
                      list(row["declared_replicated"]),
                  "leaks": list(row["leaks"])}))
    return out
