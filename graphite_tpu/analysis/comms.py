"""Static collective/ICI traffic analyzer over lowered mesh programs.

Graphite's scalability argument is that CROSS-TILE traffic — not
per-tile work — is what a distributed simulator must keep bounded; our
TPU port's analog of its socket traffic is the ICI collectives the
`parallel/px.py` packed exchange emits per protocol iteration.  Rounds
10-12 budgeted the per-iteration kernel proxy and bytes moved; this
module budgets the collective dimension the two blocked ROADMAP items
(the [T, k] mailbox compaction and the 2D campaign's real-ICI leg)
actually turn on.

The analyzer is pure static analysis over the SAME `jax.make_jaxpr`
artifacts audit/cost/identity consume (one tracing, runs on 1-device
CPU CI — the mesh programs lower over a device-less AbstractMesh).
Three layers:

  extraction   `extract_collectives` walks every shard_map/jit region
               and yields one `Collective` per collective equation
               (all_gather, ppermute, psum/pmin/pmax, all_to_all,
               reduce_scatter), each attributed to a protocol phase via
               the round-6 phase-cond structure — the SAME conds
               `cost.per_phase_costs` resolves, matched by equation
               IDENTITY (site strings are not unique: sibling eqns of
               one primitive share theirs).  A collective inside phase
               cond k belongs to phase k; one between conds belongs to
               the phase whose cond comes NEXT (it gathers that phase's
               working set); after the last cond (or in a cond-free
               vmapped program) it is "base".

  ICI pricing  per-collective payload bytes from operand avals and the
               sharded axis size, hop counts from the mesh topology:
               all_gather moves (n-1) x its shard per device over n-1
               ring hops ((n-1)/n of the full buffer per link);
               psum-likes pay the bidirectional ring all-reduce
               2(n-1)/n x the buffer; ppermute pays its payload times
               the max ring distance of its permutation; all_to_all
               and reduce_scatter (n-1)/n x the buffer.

  classification  every collective is kind "px-exchange" (the ONE
               packed descriptor `ParallelCtx.ag` emits: a full-axis
               tiled int64 all_gather — the signature the whitelist
               pins), "replication-reduction" (a full-axis psum/pmin/
               pmax, the declared way to uniformize a value), or
               "stray" — anything else, which is exactly what the
               GSPMD partitioner re-inserts when the packed exchange
               is lost (the mesh.py cliff: ~270 tiny per-scatter
               collectives per iteration, measured 16x slower).  The
               `gspmd-insertion` audit rule (rules.py) errors on every
               stray, naming its phase.

On top sit the two per-program budget metrics `collectives_per_iter`
and `ici_bytes_per_iter` (`collective_metrics` — consumed by
`cost.CostReport` and ratcheted through BUDGETS.json), the per-phase
table `tools/audit.py --comms` emits, and the tile-axis uniformity
dataflow (`shard_map_uniformity`) behind the replication-drift rule:
every shard_map output whose out_specs declare it replicated across
the tile axis must be PROVABLY uniform — no partial-axis psum leaking
a shard-dependent value into a replicated carry slot.
"""

from __future__ import annotations

import dataclasses

import jax
from jax.extend.core import Literal

from graphite_tpu.analysis.walk import (
    as_jaxpr, aval_bytes, aval_sig, call_arg_maps, iter_eqns_with_site,
    subjaxprs,
)

# Collective primitives as they appear in jaxprs.  `psum`/`pmin`/`pmax`
# carry `axes` + `axis_index_groups`; `all_gather` carries its
# `axis_size` and `tiled` flag; `ppermute` its `perm`;
# `all_to_all`/`reduce_scatter` move shards between devices.  (jax has
# no separate "all_reduce"/"collective_permute" eqn names — lax.psum IS
# the all-reduce and lax.ppermute IS the collective permute — but both
# aliases are kept in the set so a jax rename cannot silently blind the
# analyzer.)
COLLECTIVE_PRIMS = frozenset({
    "all_gather", "ppermute", "psum", "pmin", "pmax", "all_to_all",
    "reduce_scatter", "psum_scatter", "all_reduce",
    "collective_permute",
})

_PSUM_LIKE = frozenset({"psum", "pmin", "pmax", "all_reduce"})
_PERMUTE_LIKE = frozenset({"ppermute", "collective_permute"})
_SCATTERING = frozenset({"all_to_all", "reduce_scatter", "psum_scatter"})

# collective kinds (Collective.kind)
KIND_PX = "px-exchange"
KIND_REDUCTION = "replication-reduction"
KIND_STRAY = "stray"

# phase label for collectives outside every phase cond once all conds
# have passed — and for cond-free (vmapped) programs, where every
# collective is base
BASE_PHASE = "base"


def has_mesh_region(jaxpr) -> bool:
    """Does the program contain any shard_map region?  The gate for
    everything in this module: non-mesh programs have no collectives
    and get NO comms metrics (their budget entries stay byte-identical
    to the pre-round-22 ones)."""
    for _, eqn in iter_eqns_with_site(jaxpr):
        if eqn.primitive.name == "shard_map":
            return True
    return False


def mesh_axis_sizes(jaxpr) -> "dict[str, int]":
    """axis name -> size, merged over every shard_map eqn's mesh (the
    AbstractMesh the lowering traced over).  Feeds the psum-like
    pricing, whose eqns carry only axis NAMES."""
    out: "dict[str, int]" = {}
    for _, eqn in iter_eqns_with_site(jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        mesh = eqn.params.get("mesh")
        shape = getattr(mesh, "shape", None)
        if shape:
            for a, s in dict(shape).items():
                out[str(a)] = int(s)
    return out


def _collective_axes(eqn) -> "tuple[str, ...]":
    """The mesh axis names a collective eqn operates over (psum-likes
    use `axes`; the rest `axis_name`, which may be a bare string)."""
    p = eqn.params
    axes = p.get("axes") if "axes" in p else p.get("axis_name")
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(str(a) for a in axes)
    return (str(axes),)


def _over_positional_axes_only(eqn) -> bool:
    """A psum/pmin/pmax whose `axes` are all POSITIONAL (ints): what
    `vmap` leaves of a reduction over its own named axis (the sim axis
    of a campaign, `ParallelCtx.any_sim`) — a `reduce_max` over an array
    axis on one device.  No mesh axis, no fabric bytes: not a
    collective."""
    axes = eqn.params.get("axes")
    return (eqn.primitive.name in _PSUM_LIKE and bool(axes)
            and all(isinstance(a, int) for a in axes))


def _group_size(eqn) -> "int | None":
    groups = eqn.params.get("axis_index_groups")
    if not groups:
        return None
    return int(len(groups[0]))


def _ring_distance(perm, n: int) -> int:
    """Max ring distance of a ppermute's (src, dst) pairs on an n-ring
    (ICI links are bidirectional: distance d or n-d, whichever is
    shorter)."""
    best = 0
    for s, d in perm or ():
        hop = abs(int(d) - int(s)) % n
        best = max(best, min(hop, n - hop))
    return best


@dataclasses.dataclass
class Collective:
    """One collective equation in a lowered mesh program, attributed
    and priced."""

    primitive: str
    site: str
    phase: str               # protocol phase name, or BASE_PHASE
    axis_name: str           # mesh axes joined with ","
    axis_size: int           # devices participating (group size if
    #                          axis_index_groups restricts the axis)
    shape: "tuple[int, ...]"  # operand (per-device shard) shape
    dtype: str
    shard_bytes: int         # per-device operand bytes
    payload_bytes: int       # the logical full buffer (result bytes)
    ici_bytes: int           # bytes crossing ICI links, per device
    hops: int                # worst-case link hops on the ring
    kind: str                # KIND_PX | KIND_REDUCTION | KIND_STRAY

    def to_json(self) -> dict:
        return {
            "primitive": self.primitive, "site": self.site,
            "phase": self.phase, "axis": self.axis_name,
            "axis_size": self.axis_size, "shape": list(self.shape),
            "dtype": self.dtype, "shard_bytes": self.shard_bytes,
            "payload_bytes": self.payload_bytes,
            "ici_bytes": self.ici_bytes, "hops": self.hops,
            "kind": self.kind,
        }


def collective_kind(eqn) -> str:
    """Classify one collective eqn against the px-exchange whitelist.

    The packed exchange (`ParallelCtx.ag`) emits EXACTLY one shape of
    collective: a full-axis (no axis_index_groups) TILED all_gather of
    an int64 descriptor — every field widened to int64 and concatenated
    so one collective moves the whole phase's working set.  A full-axis
    psum/pmin/pmax is the declared replication reduction (the sanctioned
    way to uniformize a value across shards).  Everything else is a
    STRAY: the per-scatter collectives GSPMD inserts when the packed
    exchange is lost (mesh.py's ~270/iteration cliff), a partial-axis
    group reduction, or a permute the engine never emits."""
    name = eqn.primitive.name
    if _group_size(eqn) is not None:
        return KIND_STRAY
    if name == "all_gather":
        dtype = str(getattr(eqn.invars[0].aval, "dtype", ""))
        if eqn.params.get("tiled") and dtype == "int64":
            return KIND_PX
        return KIND_STRAY
    if name in _PSUM_LIKE:
        return KIND_REDUCTION
    return KIND_STRAY


def _price(name: str, shard_bytes: int, n: int, perm=None,
           ) -> "tuple[int, int]":
    """(ici_bytes, hops) of one collective on an n-device ring."""
    if n <= 1:
        return 0, 0
    if name == "all_gather":
        # each device contributes its shard and receives n-1 others:
        # (n-1)/n of the full n*shard buffer crosses each link
        return (n - 1) * shard_bytes, n - 1
    if name in _PSUM_LIKE:
        # bidirectional ring all-reduce: reduce-scatter + all-gather,
        # each (n-1)/n of the buffer
        return (2 * (n - 1) * shard_bytes) // n, n - 1
    if name in _PERMUTE_LIKE:
        hops = _ring_distance(perm, n)
        return shard_bytes * hops, hops
    if name in _SCATTERING:
        return ((n - 1) * shard_bytes) // n, n - 1
    return shard_bytes, n - 1


def _make_collective(eqn, site: str, phase: str,
                     axis_env: "dict[str, int]") -> Collective:
    name = eqn.primitive.name
    axes = _collective_axes(eqn)
    group = _group_size(eqn)
    if group is not None:
        n = group
    elif name == "all_gather" and "axis_size" in eqn.params:
        n = int(eqn.params["axis_size"])
    else:
        n = 1
        for a in axes:
            n *= int(axis_env.get(a, 1))
    shard_b = aval_bytes(eqn.invars[0].aval) if eqn.invars else 0
    payload_b = aval_bytes(eqn.outvars[0].aval) if eqn.outvars else 0
    sig = (aval_sig(eqn.invars[0].aval) if eqn.invars else None) \
        or ((), "?")
    ici_b, hops = _price(name, shard_b, n,
                         perm=eqn.params.get("perm"))
    return Collective(
        primitive=name, site=site, phase=phase,
        axis_name=",".join(axes), axis_size=int(n),
        shape=tuple(sig[0]), dtype=sig[1],
        shard_bytes=int(shard_b), payload_bytes=int(payload_b),
        ici_bytes=int(ici_b), hops=int(hops),
        kind=collective_kind(eqn))


def extract_collectives(jaxpr, *, n_tiles: int, phase_names=(),
                        axis_env: "dict[str, int] | None" = None,
                        ) -> "list[Collective]":
    """Every collective eqn of `jaxpr` (at any depth), phase-attributed
    and priced.

    Phase attribution matches `cost.per_phase_costs`' structure but by
    equation IDENTITY: `rules.phase_conds` enumerates the gating conds
    in DFS program order; a collective inside cond k's subtree belongs
    to phase k, a collective outside every phase cond belongs to the
    phase whose cond the walk has NOT yet passed (the px gather that
    feeds phase k runs immediately before its cond), and once all conds
    have passed — or in a cond-free vmapped program — to BASE_PHASE.

    `axis_env` supplies mesh axis sizes for collectives whose eqns
    carry only axis names (psum-likes); pass `mesh_axis_sizes(closed)`
    when walking a SUB-jaxpr of the program (e.g. the main loop body,
    which sits inside the shard_map region that binds the axes)."""
    from graphite_tpu.analysis.rules import phase_conds

    j = as_jaxpr(jaxpr)
    pcs = {id(e): k for k, (_, e) in enumerate(phase_conds(j, n_tiles))}

    def pname(k: int) -> str:
        return phase_names[k] if k < len(phase_names) else f"phase_{k}"

    out: "list[Collective]" = []
    passed = {"n": 0}

    def walk(jx, site, env, phase):
        for eqn in as_jaxpr(jx).eqns:
            name = eqn.primitive.name
            here = f"{site}.{name}" if site else name
            if name in COLLECTIVE_PRIMS \
                    and not _over_positional_axes_only(eqn):
                if phase is not None:
                    ph = pname(phase)
                elif passed["n"] < len(pcs):
                    ph = pname(passed["n"])
                else:
                    ph = BASE_PHASE
                out.append(_make_collective(eqn, here, ph, env))
                continue
            k = pcs.get(id(eqn))
            if k is not None:
                for tag, sub in subjaxprs(eqn):
                    walk(sub, f"{here}/{tag}", env, k)
                passed["n"] += 1
                continue
            env2 = env
            if name == "shard_map":
                mesh = eqn.params.get("mesh")
                shape = getattr(mesh, "shape", None)
                if shape:
                    env2 = dict(env)
                    env2.update({str(a): int(s)
                                 for a, s in dict(shape).items()})
            for tag, sub in subjaxprs(eqn):
                walk(sub, f"{here}/{tag}", env2, phase)

    walk(j, "", dict(axis_env or {}), None)
    return out


# ---------------------------------------------------------------------------
# the report + budget metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PhaseComms:
    """One protocol phase's collective traffic (the --comms table row)."""

    phase: str
    collectives: int
    ici_bytes: int
    payload_bytes: int

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CommsReport:
    """One mesh program's static collective/ICI measurements.

    The per-ITERATION view: collectives are extracted from the main
    quantum loop's body (`cost.main_loop_body`), the same per-iter
    scope the kernel/bytes budgets use, so `collectives_per_iter` and
    `ici_bytes_per_iter` move with what one protocol iteration costs
    the fabric."""

    program: str
    tiles: int
    axis_sizes: "dict[str, int]"
    collectives: "list[Collective]"

    @property
    def collectives_per_iter(self) -> int:
        return len(self.collectives)

    @property
    def ici_bytes_per_iter(self) -> int:
        return sum(c.ici_bytes for c in self.collectives)

    def strays(self) -> "list[Collective]":
        return [c for c in self.collectives if c.kind == KIND_STRAY]

    def phase_rows(self) -> "list[PhaseComms]":
        order: "list[str]" = []
        agg: "dict[str, PhaseComms]" = {}
        for c in self.collectives:
            row = agg.get(c.phase)
            if row is None:
                row = agg[c.phase] = PhaseComms(c.phase, 0, 0, 0)
                order.append(c.phase)
            row.collectives += 1
            row.ici_bytes += c.ici_bytes
            row.payload_bytes += c.payload_bytes
        return [agg[p] for p in order]

    def to_json(self) -> dict:
        return {
            "comms": True,
            "program": self.program,
            "tiles": self.tiles,
            "axis_sizes": dict(self.axis_sizes),
            "collectives_per_iter": self.collectives_per_iter,
            "ici_bytes_per_iter": self.ici_bytes_per_iter,
            "table": [r.to_json() for r in self.phase_rows()],
            "collectives": [c.to_json() for c in self.collectives],
        }


def comms_report(spec) -> CommsReport:
    """Measure one audited mesh program (an audit.ProgramSpec): the
    per-iteration collective set of its main quantum loop, phase-
    attributed.  Programs without a main while loop fall back to the
    whole program (single-quantum regions)."""
    from graphite_tpu.analysis.cost import main_loop_body

    closed = spec.closed
    env = mesh_axis_sizes(closed)
    body = main_loop_body(closed)
    scope = body if body is not None else closed
    cs = extract_collectives(
        scope, n_tiles=spec.n_tiles,
        phase_names=getattr(spec, "phase_names", ()), axis_env=env)
    return CommsReport(program=spec.name, tiles=int(spec.n_tiles),
                       axis_sizes=env, collectives=cs)


def collective_metrics(spec) -> "dict[str, int] | None":
    """The two budget metrics for `spec`, or None for a non-mesh
    program (whose BUDGETS.json entry must stay byte-identical to its
    pre-round-22 form — the metrics exist only where collectives can)."""
    if not has_mesh_region(spec.closed):
        return None
    rep = comms_report(spec)
    return {"collectives_per_iter": int(rep.collectives_per_iter),
            "ici_bytes_per_iter": int(rep.ici_bytes_per_iter)}


# ---------------------------------------------------------------------------
# tile-axis uniformity dataflow (replication-drift)
# ---------------------------------------------------------------------------

# Collectives that make their output IDENTICAL on every shard of the
# axis when run full-axis (no axis_index_groups): every device ends up
# holding the same reduction / the same gathered buffer.
_UNIFORMIZING = _PSUM_LIKE | {"all_gather"}


def _default_tile_axes() -> "tuple[str, ...]":
    from graphite_tpu.parallel.mesh import TILE_AXIS, TILE_AXIS_2D

    return (TILE_AXIS, TILE_AXIS_2D)


def _varying_outputs(jaxpr, in_varying, tile_axes, leaks, memo,
                     site=""):
    """Forward tile-variance dataflow over one jaxpr: given which
    invars hold shard-DEPENDENT values (True = varies across the tile
    axis), return the outvar variance mask.

    Sources of variance: tile-sharded inputs, `axis_index` over a tile
    axis, partial-axis (grouped) collectives, and the shard-scattering
    collectives (all_to_all / reduce_scatter).  Variance is KILLED by a
    full-axis uniformizing collective (psum-likes, all_gather) — the
    `ParallelCtx.ag` exchange is exactly such a kill, which is how the
    engine's replicated control state proves uniform.  Conds with a
    varying predicate poison every output (different shards take
    different branches); a while whose trip count can vary poisons the
    whole carry.  `leaks` collects the (site, primitive) pairs where
    variance was INTRODUCED by a collective — the named suspects a
    drift finding points at."""
    j = as_jaxpr(jaxpr)
    key = (id(j), tuple(bool(t) for t in in_varying))
    if key in memo:
        return memo[key]

    env: dict = {}
    for v, t in zip(j.invars, in_varying):
        env[v] = bool(t)

    def get(v):
        return (not isinstance(v, Literal)) \
            and env.get(v, False)

    for eqn in j.eqns:
        name = eqn.primitive.name
        here = f"{site}.{name}" if site else name
        tin = [get(v) for v in eqn.invars]
        if name == "axis_index":
            varies = str(eqn.params.get("axis_name")) in tile_axes
            for v in eqn.outvars:
                env[v] = varies
            continue
        if name in COLLECTIVE_PRIMS:
            axes = _collective_axes(eqn)
            on_tile = any(a in tile_axes for a in axes)
            grouped = _group_size(eqn) is not None
            if on_tile and grouped:
                # the leak this rule exists for: a partial-axis
                # reduction gives each GROUP its own value
                for v in eqn.outvars:
                    env[v] = True
                leaks.append((here, name))
            elif on_tile and name in _UNIFORMIZING:
                for v in eqn.outvars:
                    env[v] = False
            elif on_tile and name in _SCATTERING:
                # each shard receives a DIFFERENT piece by design
                for v in eqn.outvars:
                    env[v] = True
                leaks.append((here, name))
            else:
                # permutes (and collectives over non-tile axes) move
                # values between shards: uniform in, uniform out
                t = any(tin)
                for v in eqn.outvars:
                    env[v] = t
            continue
        subs = call_arg_maps(eqn)
        if subs:
            if name == "cond":
                pred_varies = tin[0] if tin else False
                outs = [False] * len(eqn.outvars)
                if pred_varies:
                    # different shards take different branches — every
                    # output is shard-dependent
                    outs = [True] * len(eqn.outvars)
                else:
                    for sc in subs:
                        jj = as_jaxpr(sc.jaxpr)
                        inner_in = [
                            tin[sc.in_map[i]]
                            if i < len(sc.in_map)
                            and sc.in_map[i] is not None else False
                            for i in range(len(jj.invars))]
                        inner_out = _varying_outputs(
                            jj, inner_in, tile_axes, leaks, memo, here)
                        for o, t in enumerate(inner_out):
                            if t and o < len(sc.out_map) \
                                    and sc.out_map[o] is not None:
                                outs[sc.out_map[o]] = True
                for v, t in zip(eqn.outvars, outs):
                    env[v] = t
                continue

            def inner_mask(sc, jj, marks):
                return [marks[sc.in_map[i]]
                        if i < len(sc.in_map)
                        and sc.in_map[i] is not None else False
                        for i in range(len(jj.invars))]

            # while/scan: stabilize loop-carry variance at the
            # eqn-operand level (same fixpoint shape as
            # walk.taint_narrowing), then map the stable masks through
            tin_eff = list(tin)
            for sc in subs:
                if not any(f is not None for f in sc.feedback):
                    continue
                jj = as_jaxpr(sc.jaxpr)
                for _ in range(len(jj.outvars) + 2):
                    inner_out = _varying_outputs(
                        jj, inner_mask(sc, jj, tin_eff), tile_axes,
                        leaks, memo, here)
                    changed = False
                    for o, fb in enumerate(sc.feedback):
                        if fb is None or not inner_out[o] \
                                or fb >= len(sc.in_map):
                            continue
                        op_i = sc.in_map[fb]
                        if op_i is not None and not tin_eff[op_i]:
                            tin_eff[op_i] = True
                            changed = True
                    if not changed:
                        break
            out_t = [False] * len(eqn.outvars)
            diverged = False
            for sc in subs:
                jj = as_jaxpr(sc.jaxpr)
                inner_out = _varying_outputs(
                    jj, inner_mask(sc, jj, tin_eff), tile_axes, leaks,
                    memo, here)
                if name == "while" and sc is subs[0] \
                        and any(inner_out):
                    # a varying while PREDICATE means shards run
                    # different trip counts — the whole carry diverges
                    diverged = True
                for o, t in enumerate(inner_out):
                    if t and o < len(sc.out_map) \
                            and sc.out_map[o] is not None:
                        out_t[sc.out_map[o]] = True
            if diverged:
                leaks.append((here, "while-pred"))
                out_t = [True] * len(eqn.outvars)
            for v, t in zip(eqn.outvars, out_t):
                env[v] = t
            continue
        if subs == []:  # opaque call-like: conservative pass-through
            t = any(tin)
            for v in eqn.outvars:
                env[v] = t
            continue
        # plain eqn: deterministic math on uniform operands is uniform
        t = any(tin)
        for v in eqn.outvars:
            env[v] = t

    mask = [get(v) for v in j.outvars]
    memo[key] = mask
    return mask


def _spec_has_tile(spec, tile_axes) -> bool:
    """Does one shard_map in_specs/out_specs entry (a PartitionSpec:
    per dim None, an axis name or a tuple of names) mention a tile
    axis?"""
    for entry in spec or ():
        if entry is None:
            continue
        axs = entry if isinstance(entry, (tuple, list)) else (entry,)
        if any(str(a) in tile_axes for a in axs):
            return True
    return False


def shard_map_uniformity(jaxpr, tile_axes=None) -> "list[dict]":
    """Per-shard_map uniformity audit: which outputs are DECLARED
    replicated across the tile axis (out_specs carries no tile entry)
    but not PROVABLY uniform by the variance dataflow.  Returns one row
    per shard_map region: {"site", "n_outputs", "declared_replicated",
    "non_uniform", "leaks"} — `non_uniform` non-empty means the
    replication-drift rule fires."""
    if tile_axes is None:
        tile_axes = _default_tile_axes()
    tile_axes = tuple(str(a) for a in tile_axes)
    rows = []
    for site, eqn in iter_eqns_with_site(as_jaxpr(jaxpr)):
        if eqn.primitive.name != "shard_map":
            continue
        in_specs = eqn.params["in_specs"]
        out_specs = eqn.params["out_specs"]
        body = eqn.params.get("jaxpr")
        if body is None:
            continue
        in_varying = [_spec_has_tile(n, tile_axes) for n in in_specs]
        bj = as_jaxpr(body)
        # align with the body's invars (shard_map wires 1:1)
        if len(in_varying) < len(bj.invars):
            in_varying += [False] * (len(bj.invars) - len(in_varying))
        leaks: "list[tuple[str, str]]" = []
        out_varying = _varying_outputs(
            body, in_varying[:len(bj.invars)], tile_axes, leaks, {},
            site)
        declared = [o for o, n in enumerate(out_specs)
                    if not _spec_has_tile(n, tile_axes)]
        bad = [o for o in declared
               if o < len(out_varying) and out_varying[o]]
        seen = set()
        uniq_leaks = []
        for lk in leaks:
            if lk not in seen:
                seen.add(lk)
                uniq_leaks.append({"site": lk[0], "primitive": lk[1]})
        rows.append({"site": site, "n_outputs": len(out_specs),
                     "declared_replicated": declared,
                     "non_uniform": bad, "leaks": uniq_leaks})
    return rows


# ---------------------------------------------------------------------------
# known-bad fixtures (CI self-tests)
# ---------------------------------------------------------------------------


def gspmd_insertion_fixture(tiles: int = 8, tile_shards: int = 4):
    """The known-bad program the gspmd-insertion lint must trip on: a
    shard_map region lowering the LEGACY unpacked-scatter exchange — one
    small per-field collective (a uint8 gather, an untiled int64 gather)
    inside a real phase cond, instead of the ONE packed int64 descriptor
    `ParallelCtx.ag` emits.  This is exactly the mesh.py cliff shape:
    lose the packed exchange and the partitioner re-inserts tiny
    collectives per field/scatter.  Returns an audit.ProgramSpec named
    "gspmd-fixture" whose only failing rule must be gspmd-insertion,
    with the strays attributed to the 'requester' phase (the lint's
    exit-nonzero message names it)."""
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from graphite_tpu.analysis.audit import ProgramSpec
    from graphite_tpu.parallel.mesh import TILE_AXIS_2D, _shard_map

    T, dt = int(tiles), int(tile_shards)
    mesh = AbstractMesh((dt,), (TILE_AXIS_2D,))

    def body(mail, types, times, progress):
        # mail: replicated uint8[T, T] mailbox; types/times: the
        # block-local per-lane fields the legacy layout exchanged one
        # collective EACH instead of packing
        def requester(m):
            t_full = jax.lax.all_gather(
                types, TILE_AXIS_2D, tiled=True)          # uint8: stray
            w_full = jax.lax.all_gather(
                times, TILE_AXIS_2D, tiled=False)         # untiled: stray
            row = jnp.zeros((T, T), jnp.uint8).at[0, :].set(t_full)
            bump = (w_full.sum() % 2).astype(jnp.uint8)
            return (m | row) + bump, progress + jnp.int32(1)

        def skip(m):
            return m, progress

        m2, prog = jax.lax.cond(progress < jnp.int32(4), requester,
                                skip, mail)
        return m2, prog

    fn = _shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(TILE_AXIS_2D), P(TILE_AXIS_2D), P()),
        out_specs=(P(), P()))
    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((T, T), jnp.uint8),
        jax.ShapeDtypeStruct((T,), jnp.uint8),
        jax.ShapeDtypeStruct((T,), jnp.int64),
        jax.ShapeDtypeStruct((), jnp.int32))
    return ProgramSpec(
        name="gspmd-fixture", closed=closed,
        invar_paths=["mail", "types", "times", "progress"],
        n_tiles=T, phase_names=("requester",))


def replication_drift_fixture(tiles: int = 8, tile_shards: int = 4,
                              *, leak: bool = True):
    """The replication-drift pair: a shard_map whose scalar control
    output is DECLARED replicated but computed from a psum.  With
    `leak=True` the psum is partial-axis (axis_index_groups splits the
    tile axis) — each group gets its own value, the declared
    replication is a lie, and the rule must fire naming the grouped
    psum.  With `leak=False` the psum is full-axis and the proof goes
    through.  Returns an audit.ProgramSpec."""
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from graphite_tpu.analysis.audit import ProgramSpec
    from graphite_tpu.parallel.mesh import TILE_AXIS_2D, _shard_map

    T, dt = int(tiles), int(tile_shards)
    mesh = AbstractMesh((dt,), (TILE_AXIS_2D,))
    half = list(range(dt // 2)), list(range(dt // 2, dt))
    groups = [list(g) for g in half] if leak else None

    def body(ctrl, vals):
        if groups is not None:
            part = jax.lax.psum(vals, TILE_AXIS_2D,
                                axis_index_groups=groups)
        else:
            part = jax.lax.psum(vals, TILE_AXIS_2D)
        return ctrl + part.sum()

    fn = _shard_map(body, mesh=mesh,
                    in_specs=(P(), P(TILE_AXIS_2D)), out_specs=P())
    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((), jnp.int64),
        jax.ShapeDtypeStruct((T,), jnp.int64))
    name = "drift-fixture" if leak else "drift-fixture-ok"
    return ProgramSpec(name=name, closed=closed,
                       invar_paths=["ctrl", "vals"], n_tiles=T)
