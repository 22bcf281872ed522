"""Reusable jaxpr traversal: the program auditor's walker.

jax programs arrive as nested jaxprs: `cond` carries one branch jaxpr
per arm, `while` a cond and a body, `scan`/`jit`/`remat`/custom-
derivative calls one inner jaxpr each — and `vmap` leaves no call at
all (batching rewrites eqns in place, which is exactly why a gated
cond can silently become a both-branch select under it).  Every
auditor rule (analysis/rules.py) and every structural test assertion
walks the SAME recursion below — the traversal the round-6
phase-gating test used to keep as a private `_walk_eqns` helper.

Four layers:
 - `iter_eqns` / `iter_eqns_with_site`: flat iteration over every eqn
   at every nesting depth (site strings name the path for findings);
 - `call_arg_maps`: the structural operand<->sub-jaxpr wiring of the
   call-like primitives, so dataflow analyses can cross call
   boundaries instead of stopping at them;
 - `used_invar_mask` / `taint_narrowing`: the two dataflow passes the
   rules are built on — "is this input ever consumed?" (knob-fold)
   and "does a value derived from this input get integer-narrowed?"
   (time-dtype);
 - `Scope` / `distinct_axes` / `masked_index_select`: backward value
   provenance for scatter INDEX operands — "is this index array
   provably collision-free (an iota column survives into every row)"
   and "is this the engines' masked scratch-redirect idiom" — the
   round-11 scatter-determinism rule's analysis.  Resolution follows
   def chains upward through cond/scan/jit boundaries via
   `call_arg_maps` (loop-carried positions stay unresolved: their
   value changes across iterations).
"""

from __future__ import annotations

import dataclasses

import jax
from jax.extend.core import Literal
import numpy as np


def as_jaxpr(j):
    """Normalize ClosedJaxpr | Jaxpr -> Jaxpr."""
    inner = getattr(j, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return j


def subjaxprs(eqn):
    """Yield (tag, Jaxpr) for every sub-jaxpr in eqn.params.

    Handles both ClosedJaxpr-valued params (cond branches, while
    cond/body, scan/jit jaxprs) and raw-Jaxpr values, singly or in
    tuples/lists — the same duck-typing the primitives themselves use.
    """
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for i, v in enumerate(vals):
            tag = name if len(vals) == 1 else f"{name}[{i}]"
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield tag, inner
            elif hasattr(v, "eqns"):
                yield tag, v


def is_platform_choice(eqn) -> bool:
    """A `cond` that `jax.lax.platform_dependent` made: its index is the
    lowering platform's, so lowering keeps ONE branch and no conditional
    reaches the compiler — its outputs are that branch's, not the fresh
    double buffers of a run-time `lax.cond`."""
    return (eqn.primitive.name == "cond"
            and eqn.params.get("branches_platforms") is not None)


def iter_eqns_with_site(jaxpr, _site=""):
    """Depth-first (eqn-order) walk yielding (site, eqn) at every
    nesting depth.  `site` is a readable path like
    "while/body.cond/branches[1].scatter-add"."""
    j = as_jaxpr(jaxpr)
    for eqn in j.eqns:
        here = (f"{_site}.{eqn.primitive.name}" if _site
                else eqn.primitive.name)
        yield here, eqn
        for tag, inner in subjaxprs(eqn):
            yield from iter_eqns_with_site(inner, f"{here}/{tag}")


def iter_eqns(jaxpr):
    """Every eqn of `jaxpr` and all its sub-jaxprs, depth-first."""
    for _, eqn in iter_eqns_with_site(jaxpr):
        yield eqn


def find_eqns(jaxpr, primitive_name: str):
    """All (site, eqn) whose primitive is named `primitive_name`."""
    return [(s, e) for s, e in iter_eqns_with_site(jaxpr)
            if e.primitive.name == primitive_name]


def _np_dtype(dtype):
    """numpy's dtype, or None where numpy has no word for it — a Pallas
    kernel's DMA semaphore (`dma_sem`), inside a `pallas_call`'s kernel
    jaxpr: it names itself and holds no bytes of the program's state."""
    try:
        return np.dtype(dtype)
    except TypeError:
        return None


def aval_bytes(aval) -> int:
    """Byte size of an abstract value (0 for non-array avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    dt = _np_dtype(dtype)
    return n * dt.itemsize if dt is not None else 0


def aval_sig(aval):
    """Normalized (shape, dtype-string) signature, or None."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return None
    return (tuple(int(d) for d in shape), str(_np_dtype(dtype) or dtype))


def invar_path_strings(args) -> "list[str]":
    """keystr paths of `args`' pytree leaves, in flatten order — which
    is exactly the invar order `jax.make_jaxpr(fn)(*args)` produces, so
    path i names closed.jaxpr.invars[i] (None leaves drop from both)."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(args)
    return [jax.tree_util.keystr(p) for p, _ in leaves]


# ---------------------------------------------------------------------------
# operand <-> sub-jaxpr wiring of the call-like primitives
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SubCall:
    """One sub-jaxpr of a call-like eqn plus its wiring.

    in_map[i]   = eqn operand index feeding inner invar i (None: none)
    out_map[o]  = eqn outvar index fed by inner outvar o (None: none)
    feedback[o] = inner invar index inner outvar o loops back into
                  (while/scan carries), None otherwise
    """

    jaxpr: object
    in_map: list
    out_map: list
    feedback: list


def _direct(jaxpr, eqn):
    j = as_jaxpr(jaxpr)
    n_in, n_out = len(j.invars), len(j.outvars)
    return SubCall(j, list(range(min(n_in, len(eqn.invars))))
                   + [None] * max(0, n_in - len(eqn.invars)),
                   [o if o < len(eqn.outvars) else None
                    for o in range(n_out)],
                   [None] * n_out)


def call_arg_maps(eqn) -> "list[SubCall] | None":
    """Structural wiring of a call-like eqn's sub-jaxprs.

    Returns None when the primitive has no sub-jaxprs; conservative
    1:1-mapped SubCalls for unknown call-likes whose arity lines up.
    """
    name = eqn.primitive.name
    p = eqn.params
    if name == "cond":
        out = []
        for br in p["branches"]:
            j = as_jaxpr(br)
            in_map = [k + 1 for k in range(len(j.invars))]  # skip pred
            out_map = list(range(len(j.outvars)))
            out.append(SubCall(j, in_map, out_map,
                               [None] * len(j.outvars)))
        return out
    if name == "while":
        cj, bj = as_jaxpr(p["cond_jaxpr"]), as_jaxpr(p["body_jaxpr"])
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        n_carry = len(bj.outvars)
        # eqn.invars = cond_consts + body_consts + init_carry
        cond_in = ([k for k in range(cn)]
                   + [cn + bn + k for k in range(n_carry)])
        body_in = ([cn + k for k in range(bn)]
                   + [cn + bn + k for k in range(n_carry)])
        return [
            SubCall(cj, cond_in, [None] * len(cj.outvars),
                    [None] * len(cj.outvars)),
            SubCall(bj, body_in, list(range(n_carry)),
                    [bn + k for k in range(n_carry)]),
        ]
    if name == "scan":
        j = as_jaxpr(p["jaxpr"])
        nc, ncar = p["num_consts"], p["num_carry"]
        n_out = len(j.outvars)
        return [SubCall(
            j, list(range(len(j.invars))),
            list(range(n_out)),
            [nc + k if k < ncar else None for k in range(n_out)])]
    if name in _DIRECT_CALLS:
        j = p.get("jaxpr") or p.get("call_jaxpr") or p.get("fun_jaxpr")
        if j is not None and hasattr(as_jaxpr(j), "eqns"):
            return [_direct(j, eqn)]
        return None
    # unknown primitive: if it carries sub-jaxprs whose invar count
    # matches the eqn's operand count, assume direct wiring
    subs = list(subjaxprs(eqn))
    if not subs:
        return None
    out = []
    for _, j in subs:
        jj = as_jaxpr(j)
        if len(jj.invars) == len(eqn.invars):
            out.append(_direct(jj, eqn))
        else:
            return []  # sub-jaxprs exist but wiring unknown: signal "opaque"
    return out


# ---------------------------------------------------------------------------
# dataflow pass 1: is an input ever consumed?  (knob-fold)
# ---------------------------------------------------------------------------


def used_invar_mask(jaxpr, *, count_outvars=False, _memo=None) -> "list[bool]":
    """Per-invar flag: does anything in the (recursively walked) program
    consume this input?

    An invar is "used" when it feeds any eqn — for call-like eqns, only
    when the corresponding inner invar is itself used (recursively), so
    a value merely threaded through a while carry untouched does not
    count at the top level unless `count_outvars` (inner jaxprs pass
    True: their outputs flow onward).  Over-approximates liveness (an
    eqn computing a dead value still counts as a use) — make_jaxpr
    output is not DCE'd, and tracing never records a value nothing
    consumed, so the approximation errs loud, not silent.
    """
    if _memo is None:
        _memo = {}
    j = as_jaxpr(jaxpr)
    key = (id(j), bool(count_outvars))
    if key in _memo:
        return _memo[key]
    used = set()
    if count_outvars:
        for v in j.outvars:
            if not isinstance(v, Literal):
                used.add(v)
    for eqn in j.eqns:
        subs = call_arg_maps(eqn)
        if subs is None:
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    used.add(v)
        elif not subs:  # opaque call-like: conservatively all-used
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    used.add(v)
        else:
            for sc in subs:
                inner = used_invar_mask(sc.jaxpr, count_outvars=True,
                                        _memo=_memo)
                for i, u in enumerate(inner):
                    if u and i < len(sc.in_map) \
                            and sc.in_map[i] is not None:
                        v = eqn.invars[sc.in_map[i]]
                        if not isinstance(v, Literal):
                            used.add(v)
    mask = [v in used for v in j.invars]
    _memo[key] = mask
    return mask


# ---------------------------------------------------------------------------
# dataflow pass 2: forward time-taint + integer-narrowing detection
# ---------------------------------------------------------------------------

# Primitives through which "absolute simulated time" does NOT propagate:
# differences (latencies/deltas — legitimately int32, time_types.
# DELTA_DTYPE), ratios/remainders (quantum phases, ring slots),
# predicates, bit twiddling, and index-producing reductions.
TAINT_STOP = frozenset({
    "sub", "div", "rem", "eq", "ne", "lt", "le", "gt", "ge",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "argmin", "argmax", "reduce_and",
    "reduce_or", "iota", "sign", "population_count", "clz",
    "is_finite", "stop_gradient",
})

_INT_KINDS = ("i", "u")


def _is_narrowing(old_dtype, new_dtype) -> bool:
    o, n = np.dtype(old_dtype), np.dtype(new_dtype)
    return (o.kind in _INT_KINDS and n.kind in _INT_KINDS
            and n.itemsize < o.itemsize)


def taint_narrowing(jaxpr, in_taint, on_finding=None, _site="",
                    _depth=0) -> "list[bool]":
    """Forward taint from `in_taint`-marked invars; report every integer
    narrowing of a tainted value via `on_finding(site, eqn, old, new)`.

    Taint propagates through value-preserving/monotone arithmetic (add,
    mul, min/max, selects, data movement, scatters, reductions) and
    crosses call boundaries (cond/while/scan/jit) via `call_arg_maps`,
    iterating loop carries to a fixpoint.  It STOPS at `TAINT_STOP` —
    a difference of two absolute clocks is a delta, which the engine
    legitimately keeps in int32 (time_types.DELTA_DTYPE).  Returns the
    outvar taint mask.
    """
    j = as_jaxpr(jaxpr)
    env = {}
    for v, t in zip(j.invars, in_taint):
        env[v] = bool(t)

    def get(v):
        return (not isinstance(v, Literal)) and env.get(v, False)

    for eqn in j.eqns:
        site = (f"{_site}.{eqn.primitive.name}" if _site
                else eqn.primitive.name)
        tin = [get(v) for v in eqn.invars]
        name = eqn.primitive.name
        subs = call_arg_maps(eqn)
        if subs:
            out_taint = [False] * len(eqn.outvars)

            def inner_taint(sc, jj, marks):
                return [marks[sc.in_map[i]]
                        if i < len(sc.in_map)
                        and sc.in_map[i] is not None else False
                        for i in range(len(jj.invars))]

            # Stabilize loop-carry taint FIRST, at the eqn-operand
            # level: a carry that becomes tainted in a later iteration
            # taints that operand position for EVERY sub-jaxpr —
            # including the while-COND's copy of it, which has no
            # feedback edges of its own (a narrowing in the loop
            # condition must still be reported).
            tin_eff = list(tin)
            for sc in subs:
                if not any(f is not None for f in sc.feedback):
                    continue
                jj = as_jaxpr(sc.jaxpr)
                for _ in range(len(jj.outvars) + 2):
                    inner_out = taint_narrowing(
                        jj, inner_taint(sc, jj, tin_eff), None, site,
                        _depth + 1)
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
            # one reporting pass per sub-jaxpr with the stable marks
            for sc in subs:
                jj = as_jaxpr(sc.jaxpr)
                inner_out = taint_narrowing(
                    jj, inner_taint(sc, jj, tin_eff), on_finding, site,
                    _depth + 1)
                for o, t in enumerate(inner_out):
                    if t and o < len(sc.out_map) \
                            and sc.out_map[o] is not None:
                        out_taint[sc.out_map[o]] = True
            for v, t in zip(eqn.outvars, out_taint):
                env[v] = t
            continue
        if subs == []:  # opaque call-like: conservative taint-through
            t = any(tin)
            for v in eqn.outvars:
                env[v] = t
            continue
        if name == "convert_element_type":
            old = getattr(eqn.invars[0].aval, "dtype", None)
            new = eqn.params.get("new_dtype")
            if tin[0] and old is not None and new is not None \
                    and _is_narrowing(old, new):
                if on_finding is not None:
                    on_finding(site, eqn, old, new)
                env[eqn.outvars[0]] = False  # reported; don't cascade
            else:
                env[eqn.outvars[0]] = tin[0]
            continue
        if name.startswith("scatter"):
            # scatter(operand, indices, updates): tainted updates landing
            # in a narrower accumulator is an int32 time accumulation
            upd_i = 2 if len(eqn.invars) > 2 else len(eqn.invars) - 1
            tgt = getattr(eqn.invars[0].aval, "dtype", None)
            upd = getattr(eqn.invars[upd_i].aval, "dtype", None)
            if tin[upd_i] and tgt is not None and upd is not None \
                    and _is_narrowing(upd, tgt):
                if on_finding is not None:
                    on_finding(site, eqn, upd, tgt)
                env[eqn.outvars[0]] = False
            else:
                env[eqn.outvars[0]] = tin[0] or tin[upd_i]
            continue
        tainted = any(tin) and name not in TAINT_STOP
        for v in eqn.outvars:
            env[v] = tainted
    return [get(v) for v in j.outvars]


# ---------------------------------------------------------------------------
# dataflow pass 3: backward index provenance (scatter-determinism)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Scope:
    """One jaxpr nesting level of a provenance walk: def sites at this
    level plus the wiring back to the enclosing level, so a value that
    enters a cond branch (or a scan body's const slot) as an invar can
    be chased to its real definition outside."""

    jaxpr: object                  # the (raw) Jaxpr of this level
    defs: dict                     # var -> defining eqn at this level
    parent: "Scope | None" = None
    parent_eqn: object | None = None   # the call-like eqn that owns us
    sub: "SubCall | None" = None       # our wiring inside parent_eqn
    consts: dict = dataclasses.field(default_factory=dict)
    # var -> concrete array for the top ClosedJaxpr's constvars — lets
    # the analysis check hoisted np.arange tables for real uniqueness


def make_scope(jaxpr, parent=None, parent_eqn=None, sub=None,
               consts: "dict | None" = None) -> Scope:
    j = as_jaxpr(jaxpr)
    defs = {}
    for eqn in j.eqns:
        for v in eqn.outvars:
            defs[v] = eqn
    return Scope(j, defs, parent, parent_eqn, sub, consts or {})


def scope_from_closed(closed) -> Scope:
    """Top-level Scope of a ClosedJaxpr, with its consts resolvable."""
    j = as_jaxpr(closed)
    consts = {}
    for v, c in zip(j.constvars, getattr(closed, "consts", ()) or ()):
        consts[v] = np.asarray(c) if hasattr(c, "shape") else c
    return make_scope(j, consts=consts)


def resolve_var(var, scope: Scope):
    """Chase `var` one level up when it is an invar of `scope.jaxpr`:
    returns (outer_var, outer_scope, axis_shift) or (None, None, 0)
    when the definition cannot be followed (top-level input, loop
    carry, opaque wiring).  axis_shift is 1 when the outer value is a
    scan xs operand the body sees one leading axis short."""
    if scope.parent is None or scope.sub is None:
        return None, None, 0
    try:
        i = list(scope.jaxpr.invars).index(var)
    except ValueError:
        return None, None, 0
    sub = scope.sub
    # loop-carried slots change value across iterations: unresolvable
    if any(fb == i for fb in sub.feedback if fb is not None):
        return None, None, 0
    if i >= len(sub.in_map) or sub.in_map[i] is None:
        return None, None, 0
    if scope.parent_eqn.primitive.name == "while":
        # the while COND's SubCall carries no feedback edges of its
        # own, but its carry slots are just as iteration-variant as
        # the body's: everything past the two const blocks is carry
        cn = scope.parent_eqn.params["cond_nconsts"]
        bn = scope.parent_eqn.params["body_nconsts"]
        if sub.in_map[i] >= cn + bn:
            return None, None, 0
    outer = scope.parent_eqn.invars[sub.in_map[i]]
    if isinstance(outer, Literal):
        return None, None, 0
    shift = 0
    if scope.parent_eqn.primitive.name == "scan":
        r_out = len(getattr(outer.aval, "shape", ()) or ())
        r_in = len(getattr(var.aval, "shape", ()) or ())
        if r_out == r_in + 1:
            shift = 1   # an xs operand: the body sees slice [l, ...]
    return outer, scope.parent, shift


# Per-axis provenance forms (the value of _axis_forms):
#   ("D",)       distinct: any two positions differing in this axis
#                hold different values (no congruence info — e.g. a
#                concrete const table checked exhaustively)
#   (m, c)       affine-congruent: value = pos + c exactly when m == 0,
#                else value ≡ pos + c (mod m).  c may be None for an
#                unknown-but-uniform shift (e.g. pos + traced_scalar).
#                Distinct along an axis of size n iff m == 0 or m >= n.
# The congruence form is what survives the engines' wraparound idiom
# (`jnp.where(h < T, h, h - T)` -> select_n of pos+c1 / pos+c2 arms:
# both ≡ pos mod |c1-c2|, still collision-free at the axis size).


def _const_axis_forms(arr) -> dict:
    """("D",) for every axis of a concrete array along which all pairs
    of positions differ (checked exhaustively — consts are host-side
    and small)."""
    arr = np.asarray(arr)
    out = {}
    for a in range(arr.ndim):
        m = np.moveaxis(arr, a, 0).reshape(arr.shape[a], -1)
        # need every pair of rows to differ in EVERY column
        if all(len(np.unique(m[:, c])) == m.shape[0]
               for c in range(m.shape[1])):
            out[a] = ("D",)
    return out


_DISTINCT_PASS_THROUGH = frozenset({
    "convert_element_type", "copy", "stop_gradient",
    # jnp.asarray(host_const) inserts a device_put between a hoisted
    # index table and its use — value-preserving movement, without
    # which Scope.consts/_const_axis_forms is unreachable
    "device_put",
})

# Call primitives that wire operands 1:1 into one inner jaxpr, by the
# installed jax's names (shared with cost._CALL_PRIMITIVES).
_DIRECT_CALLS = frozenset({
    "jit", "closed_call", "custom_jvp_call", "custom_vjp_call", "remat2",
})

_PROVENANCE_DEPTH = 24


def _descend_outvar(eqn, var, scope: Scope):
    """When `var` is an output of a direct-call eqn (jit et al — the
    wrappers jnp.where/jnp.mod lowerings hide behind), step INTO the
    sub-jaxpr: returns (inner outvar, inner Scope) or None."""
    if eqn.primitive.name not in _DIRECT_CALLS:
        return None
    subs = call_arg_maps(eqn)
    if not subs:
        return None
    sub = subs[0]
    try:
        o = list(eqn.outvars).index(var)
    except ValueError:
        return None
    for io, oo in enumerate(sub.out_map):
        if oo == o:
            inner = as_jaxpr(sub.jaxpr).outvars[io]
            if isinstance(inner, Literal):
                return None
            return inner, make_scope(sub.jaxpr, scope, eqn, sub)
    return None


def _scalar_literal(v, scope: Scope):
    """The Python value of a scalar literal (chasing trivial
    broadcasts/converts), or None."""
    for _ in range(6):
        if isinstance(v, Literal):
            val = np.asarray(v.val)
            return val.item() if val.ndim == 0 else None
        if getattr(v.aval, "shape", None) == () and v in scope.consts:
            return np.asarray(scope.consts[v]).item()
        e = scope.defs.get(v)
        if e is None or e.primitive.name not in (
                "broadcast_in_dim", "convert_element_type", "reshape",
                "squeeze", "copy"):
            return None
        v = e.invars[0]
    return None


def _peel_uniform_shift(v, scope: Scope):
    """Resolve `v` to (base var, base scope, accumulated literal shift)
    by peeling add/sub of scalar literals and trivial wrappers — the
    shape of a wrap-fixup select arm (`t` and `t - T` share base `t`
    with shifts 0 and -T).  Returns None when `v` is a literal or the
    chain leaves the provable shape."""
    if isinstance(v, Literal):
        return None
    shift = 0
    for _ in range(12):
        eqn = scope.defs.get(v)
        if eqn is None:
            v2, s2, sh = resolve_var(v, scope)
            if v2 is None or sh:
                break
            v, scope = v2, s2
            continue
        down = _descend_outvar(eqn, v, scope)
        if down is not None:
            v, scope = down
            continue
        name = eqn.primitive.name
        if name in ("add", "sub"):
            x, y = eqn.invars[0], eqn.invars[1]
            k = _scalar_literal(y, scope)
            if k is not None and not isinstance(x, Literal):
                shift += -int(k) if name == "sub" else int(k)
                v = x
                continue
            if name == "add":
                k = _scalar_literal(x, scope)
                if k is not None \
                        and not isinstance(y, Literal):
                    shift += int(k)
                    v = y
                    continue
            break
        if name in _DISTINCT_PASS_THROUGH:
            v = eqn.invars[0]
            continue
        break
    return v, scope, shift


def _const_cross_shift_distinct(arr, axis: int, shifts) -> bool:
    """For a concrete index table: can two positions along `axis` (same
    other coordinates) collide under ANY per-position choice of the
    literal `shifts`?  Exhaustive, like _const_axis_forms — this is
    what lets a no-repeat const table stay proven through the .at[]
    wrap-fixup select (`select(d < 0, d, d + N)`), whose arms shift
    the same base by different amounts."""
    arr = np.asarray(arr)
    m = np.moveaxis(arr, axis, 0).reshape(arr.shape[axis], -1)
    shifts = sorted({int(s) for s in shifts})
    for c in range(m.shape[1]):
        seen = {}
        for i, x in enumerate(m[:, c]):
            for s in shifts:
                key = int(x) + s
                if seen.setdefault(key, i) != i:
                    return False
    return True


def _is_uniform_scalar(v, scope: Scope, _depth: int = 0) -> bool:
    """Does `v` hold one value replicated everywhere (a broadcast of a
    scalar)?  Adding such an operand shifts every position equally, so
    per-axis distinctness survives even when the value is traced; a
    select arm like this is a single redirect slot."""
    if _depth > 12:
        return False
    while True:
        if isinstance(v, Literal):
            val = np.asarray(v.val)
            return val.ndim == 0 or len(np.unique(val)) == 1
        if getattr(v.aval, "shape", None) == ():
            return True
        eqn = scope.defs.get(v)
        if eqn is not None:
            down = _descend_outvar(eqn, v, scope)
            if down is None:
                break
            v, scope = down
            continue
        if v in scope.consts:
            c = np.asarray(scope.consts[v])
            return c.size == 1 or len(np.unique(c)) == 1
        v2, s2, shift = resolve_var(v, scope)
        if v2 is None:
            return False
        v, scope = v2, s2
    # broadcasting/reshaping a uniform value stays uniform
    if eqn.primitive.name in (
            "broadcast_in_dim", "reshape", "squeeze", "copy",
            "convert_element_type", "stop_gradient", "expand_dims"):
        return _is_uniform_scalar(eqn.invars[0], scope, _depth + 1)
    return False


def _merge_arm_forms(forms: "list") -> "tuple | None":
    """Combine per-arm forms of an elementwise select: every position
    takes SOME arm's value, so the result is congruent mod the gcd of
    the arms' moduli and pairwise offset differences."""
    if any(f is None for f in forms):
        return None
    if all(f == ("D",) for f in forms) and len(forms) == 1:
        return ("D",)
    if any(f == ("D",) for f in forms):
        return None   # no congruence info to reconcile the arms with
    if any(f[1] is None for f in forms):
        # unknown shifts: offset differences unprovable across arms
        return forms[0] if len(forms) == 1 else None
    g = 0
    for f in forms:
        g = int(np.gcd(g, int(f[0])))
    c0 = forms[0][1]
    for f in forms[1:]:
        g = int(np.gcd(g, abs(int(f[1]) - int(c0))))
    return (g, c0 % g if g else c0)


def _axis_forms(var, scope: Scope, _depth: int = 0) -> dict:
    """axis -> provenance form (see above) for `var`.  Conservative:
    a missing axis means "not provable", never "aliasing"."""
    if _depth > _PROVENANCE_DEPTH or isinstance(var, Literal):
        return {}
    while True:
        eqn = scope.defs.get(var)
        if eqn is not None:
            down = _descend_outvar(eqn, var, scope)
            if down is None:
                break
            var, scope = down
            continue
        if var in scope.consts:
            return _const_axis_forms(scope.consts[var])
        var2, scope2, shift = resolve_var(var, scope)
        if var2 is None:
            return {}
        if shift:
            outer = _axis_forms(var2, scope2, _depth + 1)
            return {a - 1: f for a, f in outer.items() if a >= 1}
        var, scope = var2, scope2
    name = eqn.primitive.name
    if name == "iota":
        return {int(eqn.params["dimension"]): (0, 0)}
    if name in _DISTINCT_PASS_THROUGH:
        return _axis_forms(eqn.invars[0], scope, _depth + 1)
    if name in ("add", "sub"):
        x, y = eqn.invars[0], eqn.invars[1]
        # value = structured + uniform shift: distinctness survives,
        # and a literal shift keeps the congruence offset exact
        candidates = [(x, y, -1 if name == "sub" else 1)]
        if name == "add":
            candidates.append((y, x, 1))
        for a, b, sign in candidates:
            if isinstance(a, Literal) \
                    or not _is_uniform_scalar(b, scope):
                continue
            forms = _axis_forms(a, scope, _depth + 1)
            k = _scalar_literal(b, scope)
            out = {}
            for ax, f in forms.items():
                if f == ("D",):
                    out[ax] = f
                elif k is None or f[1] is None:
                    out[ax] = (f[0], None)
                else:
                    c = int(f[1]) + sign * int(k)
                    out[ax] = (f[0], c % f[0] if f[0] else c)
            return out
        return {}
    if name == "rem":
        r = _scalar_literal(eqn.invars[1], scope)
        if r is None or int(r) <= 0:
            return {}
        r = int(r)
        forms = _axis_forms(eqn.invars[0], scope, _depth + 1)
        out = {}
        for ax, f in forms.items():
            if f == ("D",):
                continue   # remainder of an arbitrary table can collide
            m, c = f
            if m == 0 or m % r == 0:
                out[ax] = (r, None if c is None else int(c) % r)
        return out
    if name == "select_n":
        # shared-base arms first (the .at[] wrap fixup: select(p, t,
        # t - T)): the arms' absolute offsets may be unknown, but
        # their RELATIVE literal shifts still pin congruence mod the
        # shift gcd — per position the value is base + shift_j, so
        # distinctness mod gcd(base modulus, shift differences) holds
        peeled = [_peel_uniform_shift(v, scope)
                  for v in eqn.invars[1:]]
        if len(peeled) > 1 and all(p is not None for p in peeled):
            b0, s0, k0 = peeled[0]
            if all(p[0] is b0 and p[1].jaxpr is s0.jaxpr
                   for p in peeled[1:]):
                g = 0
                for _, _, k in peeled[1:]:
                    g = int(np.gcd(g, abs(int(k) - int(k0))))
                cval = s0.consts.get(b0)
                shifts = [k0] + [p[2] for p in peeled[1:]]
                out = {}
                for ax, f in _axis_forms(b0, s0, _depth + 1).items():
                    if f == ("D",):
                        # identical shifts are a pure copy; differing
                        # shifts keep a CONST table distinct exactly
                        # when no cross-shift pair collides (checked
                        # exhaustively, consts are small)
                        if g == 0 or (cval is not None
                                      and _const_cross_shift_distinct(
                                          cval, ax, shifts)):
                            out[ax] = f
                        continue
                    if g == 0:
                        m, c = int(f[0]), f[1]
                        out[ax] = (m, None if c is None
                                   else (int(c) + k0) % m if m
                                   else int(c) + k0)
                        continue
                    m = int(np.gcd(int(f[0]), g))
                    if m:
                        out[ax] = (m, None if f[1] is None
                                   else (int(f[1]) + k0) % m)
                return out
        arms = [
            _axis_forms(v, scope, _depth + 1)
            if not isinstance(v, Literal) else {}
            for v in eqn.invars[1:]
        ]
        out = {}
        for ax in set().union(*[set(a) for a in arms]) if arms else ():
            merged = _merge_arm_forms([a.get(ax) for a in arms])
            if merged is not None:
                out[ax] = merged
        return out
    if name == "broadcast_in_dim":
        inner = _axis_forms(eqn.invars[0], scope, _depth + 1)
        bd = eqn.params["broadcast_dimensions"]
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        return {
            int(bd[a]): f for a, f in inner.items()
            if a < len(bd) and int(in_shape[a]) ==
            int(eqn.outvars[0].aval.shape[bd[a]])
        }
    if name in ("reshape", "squeeze"):
        # only size-1 insertions/removals are tracked: the non-unit
        # dims must survive in order for the axis map to be sound
        in_shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
        out_shape = tuple(eqn.outvars[0].aval.shape)
        in_nz = [a for a, d in enumerate(in_shape) if d != 1]
        out_nz = [a for a, d in enumerate(out_shape) if d != 1]
        if [in_shape[a] for a in in_nz] != [out_shape[a] for a in out_nz]:
            return {}
        inner = _axis_forms(eqn.invars[0], scope, _depth + 1)
        remap = dict(zip(in_nz, out_nz))
        return {remap[a]: f for a, f in inner.items() if a in remap}
    if name == "concatenate":
        d = int(eqn.params["dimension"])
        out = {}
        for v in eqn.invars:
            for a, f in _axis_forms(v, scope, _depth + 1).items():
                if a != d and a not in out:
                    out[a] = f
        return out
    return {}


def distinct_axes(var, scope: Scope) -> frozenset:
    """Axes `a` of `var` with the pairwise-distinct property: any two
    positions differing in axis `a` hold different values, regardless
    of the other coordinates.  Proven by provenance (`_axis_forms`):
    an iota column, a concrete const table with no repeats, or an
    affine-congruent form whose modulus covers the axis size (the
    wraparound-select idiom).  Conservative: an empty set means "not
    provable", not "aliasing"."""
    shape = tuple(getattr(var.aval, "shape", ()) or ())
    out = set()
    for a, f in _axis_forms(var, scope).items():
        if a >= len(shape):
            continue
        if f == ("D",) or f[0] == 0 or f[0] >= int(shape[a]):
            out.add(a)
    return frozenset(out)


def masked_index_select(var, scope: Scope, _depth: int = 0) -> bool:
    """Is `var` an index array built by the engines' masked
    scratch-redirect idiom — a select between real indices and a
    uniform scratch slot (`jnp.where(mask, word, SCRATCH)`), the
    round-9 "masked store" shape?  Such a scatter is masked BY
    CONSTRUCTION: disabled lanes all land on the dedicated slot.  The
    detection sees through jnp's jit-wrapped where/mod composites and
    the index-wrap fixup select the `.at[]` lowering adds on top."""
    if _depth > _PROVENANCE_DEPTH or isinstance(var, Literal):
        return False
    while True:
        eqn = scope.defs.get(var)
        if eqn is not None:
            down = _descend_outvar(eqn, var, scope)
            if down is None:
                break
            var, scope = down
            continue
        var2, scope2, shift = resolve_var(var, scope)
        if var2 is None or shift:
            return False
        var, scope = var2, scope2
    name = eqn.primitive.name
    if name in _DISTINCT_PASS_THROUGH or name in (
            "broadcast_in_dim", "reshape", "squeeze", "concatenate",
            "add", "sub", "rem"):
        # index arithmetic (the .at[] wrap fixup adds/rems the axis
        # size) and movement preserve "one arm is a fixed slot" ONLY
        # when every operand is the masked select or uniform: a masked
        # redirect added to an OPAQUE base (base + where(mask, 0, S))
        # re-opens collisions between the base rows, and an opaque
        # part concatenated next to a masked one can alias it
        got_masked = False
        for v in eqn.invars:
            if isinstance(v, Literal) \
                    or _is_uniform_scalar(v, scope):
                continue
            if not masked_index_select(v, scope, _depth + 1):
                return False
            got_masked = True
        return got_masked
    if name != "select_n":
        return False

    def is_uniform_arm(v):
        # a literal, a broadcast scalar, or anything else uniform:
        # every masked-off lane lands on ONE slot
        return isinstance(v, Literal) \
            or _is_uniform_scalar(v, scope)

    # select_n(pred, arm0, arm1, ...): one arm a uniform scratch slot
    # (the masked-store idiom proper), else EVERY arm itself a masked
    # select (the wrap fixup selects between two shifted copies of the
    # redirect) — an opaque sibling arm re-opens collisions between
    # the lanes that select it
    if any(is_uniform_arm(v) for v in eqn.invars[1:]):
        return True
    got_masked = False
    for v in eqn.invars[1:]:
        if not masked_index_select(v, scope, _depth + 1):
            return False
        got_masked = True
    return got_masked


def scatter_row_axes(eqn) -> "tuple[int, ...]":
    """The index-row axes of a scatter's indices operand: everything
    except the trailing index-vector dim and any vmap batching dims
    (a batching dim addresses a DIFFERENT operand slice per position,
    so it cannot alias across itself)."""
    idx = eqn.invars[1]
    rank = len(getattr(idx.aval, "shape", ()) or ())
    dn = eqn.params.get("dimension_numbers")
    batch = tuple(getattr(dn, "scatter_indices_batching_dims", ()) or ())
    return tuple(a for a in range(rank - 1) if a not in batch)


def scatter_writer_proof(eqn, scope: Scope) -> "str | None":
    """Name of the proof that this scatter writes every target cell at
    most once (each cell has a SINGLE writer within the op), or None
    when no proof holds.  The proof ladder, in order:

      "unique-indices"  the op declares unique_indices=True — the
                        caller asserts non-aliasing and XLA is allowed
                        to exploit it, so a lie is already UB
      "constant-index"  the index operand is a literal — a fixed,
                        statically visible row set (treated as the
                        author's explicit layout, like the old
                        scatter-determinism literal skip)
      "single-row"      every non-batching row axis has size 1 (or
                        there are none): one row per addressed slice
                        cannot collide with itself
      "distinct-axes"   index provenance shows the one multi-row axis
                        is pairwise-distinct (an iota column survives
                        into every row — `distinct_axes`)
      "masked-select"   the masked scratch-redirect idiom: disabled
                        lanes all land on one spill slot
                        (`masked_index_select`)

    Sound for at most ONE multi-row axis, same as scatter-determinism:
    per-axis distinctness covers pairs differing in one axis only."""
    if eqn.params.get("unique_indices"):
        return "unique-indices"
    idx = eqn.invars[1]
    if isinstance(idx, Literal):
        return "constant-index"
    idx_shape = tuple(getattr(idx.aval, "shape", ()) or ())
    rows = tuple(a for a in scatter_row_axes(eqn) if idx_shape[a] > 1)
    if not rows:
        return "single-row"
    if len(rows) == 1 and rows[0] in distinct_axes(idx, scope):
        return "distinct-axes"
    if masked_index_select(idx, scope):
        return "masked-select"
    return None
