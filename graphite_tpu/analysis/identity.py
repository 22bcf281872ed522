"""Program identity: canonical jaxpr fingerprints + structural diffs.

Graphite's credibility rests on knowing exactly which artifact was
measured — the paper's lax-sync comparisons only mean something because
the simulated program is held fixed while sync schemes vary.  The repo
now has three consumers of "the lowered program" (the round-8 auditor,
the round-10 cost/budget gate, `SweepRunner`'s zero-recompile
campaigns) and, until this module, three ad-hoc notions of whether two
programs are the same: `str(jaxpr)` comparisons in tests, hand-written
names keying `BUDGETS.json`, and nothing at all for the campaign cache.

Two tools, one definition of identity:

  fingerprint(closed)
      A canonical digest of a ClosedJaxpr.  The traversal assigns
      variables alpha-renaming-invariant numbers (first-appearance
      order per scope), recurses into every sub-jaxpr (cond branches,
      while cond/body, scan/jit bodies), normalizes literals and
      params (arrays hash by shape/dtype/bytes; dicts sort; callables
      reduce to their names; memory addresses are scrubbed), and
      sha256-hashes the token stream.  Two traces of the same config
      produce the SAME fingerprint even though `str(jaxpr)` differs in
      var names and jax-version printing details; one changed literal,
      trip count or carried aval produces a different one.

  structural_diff(a, b)
      Given two programs whose fingerprints differ, walk them in
      LOCKSTEP and name the first divergent equation — with the same
      phase attribution `analysis/cost.py` uses (the round-6
      phase-cond structure), so a regression report says "mesi
      `home_commit` phase gained a 96 MB while-carry", not "hash
      changed".

`analysis/registry.py` builds the program registry + `PROGRAMS.lock`
on top; `tools/audit.py --lock` gates CI with it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import re

import jax
from jax.extend.core import Literal
import numpy as np

from graphite_tpu.analysis.walk import as_jaxpr, aval_bytes, aval_sig

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")

FINGERPRINT_SCHEME = "gfp1"   # bump when the canonical form changes


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------


def _norm_array(a) -> str:
    a = np.asarray(a)
    if a.ndim == 0:
        # scalars print by value: cheap, and diffs stay readable
        return f"{a.dtype}:{a.item()!r}"
    digest = hashlib.sha256(np.ascontiguousarray(a).tobytes())
    return f"{a.dtype}{list(a.shape)}:{digest.hexdigest()[:16]}"


def _norm_param(v, emit_jaxpr) -> str:
    """One param value as a deterministic token.  `emit_jaxpr` renders
    nested (Closed)Jaxprs through the main canonicalizer so sub-program
    structure is part of the parent's identity."""
    if v is None or isinstance(v, (bool, int, str)):
        return repr(v)
    if isinstance(v, float):
        return repr(float(v))
    if isinstance(v, (tuple, list)):
        return "[" + ",".join(_norm_param(x, emit_jaxpr) for x in v) + "]"
    if isinstance(v, (set, frozenset)):
        # iteration order follows the per-process string hash seed
        # (shard_map's manual_axes is a frozenset of axis names)
        return "{" + ",".join(sorted(
            _norm_param(x, emit_jaxpr) for x in v)) + "}"
    if isinstance(v, dict):
        return "{" + ",".join(
            f"{k!r}:{_norm_param(v[k], emit_jaxpr)}"
            for k in sorted(v, key=repr)) + "}"
    if hasattr(v, "eqns") or hasattr(getattr(v, "jaxpr", None), "eqns"):
        return emit_jaxpr(v)
    if isinstance(v, (np.ndarray, np.generic)) or hasattr(v, "__array__"):
        try:
            return _norm_array(v)
        except Exception:  # noqa: BLE001 — fall through to repr
            pass
    if isinstance(v, np.dtype) or (isinstance(v, type)
                                   and issubclass(v, np.generic)):
        return str(np.dtype(v))
    if callable(v):
        return f"<fn {getattr(v, '__name__', type(v).__name__)}>"
    # named tuples (GatherDimensionNumbers etc.), enums, shardings:
    # deterministic reprs modulo memory addresses, which we scrub
    return _ADDR_RE.sub("0x*", repr(v))


def _aval_token(aval) -> str:
    sig = aval_sig(aval)
    if sig is None:
        return str(type(aval).__name__)
    return f"{sig[1]}{list(sig[0])}"


class _Canon:
    """One canonicalization pass: emits the token stream."""

    def __init__(self):
        self.lines: "list[str]" = []

    def operand(self, v, env: dict) -> str:
        if isinstance(v, Literal):
            val = v.val
            if hasattr(val, "shape") or isinstance(val, np.generic):
                return f"lit({_norm_array(val)})"
            return f"lit({val!r})"
        n = env.get(v)
        if n is None:
            # a free var from an enclosing scope (legacy-style jaxprs);
            # number it on first sight so references stay stable
            n = env[v] = ("^", len(env))
        return f"v{n[1]}:{_aval_token(v.aval)}" \
            if n[0] == "" else f"^{n[1]}:{_aval_token(v.aval)}"

    def jaxpr(self, j, consts=(), depth=0) -> str:
        jj = as_jaxpr(j)
        inner_consts = getattr(j, "consts", None)
        if inner_consts is None:
            inner_consts = consts
        env = {}
        for v in list(jj.constvars) + list(jj.invars):
            env[v] = ("", len(env))
        pre = "  " * depth
        self.lines.append(
            pre + "jaxpr{" + " in=["
            + ",".join(_aval_token(v.aval)
                       for v in list(jj.constvars) + list(jj.invars))
            + "]")
        for i, c in enumerate(inner_consts or ()):
            try:
                self.lines.append(pre + f" const{i}={_norm_array(c)}")
            except Exception:  # noqa: BLE001 — non-array const
                self.lines.append(pre + f" const{i}="
                                  + _ADDR_RE.sub("0x*", repr(c)))
        for eqn in jj.eqns:
            ins = ",".join(self.operand(v, env) for v in eqn.invars)
            sub_tokens = []

            def emit_sub(v):
                start = len(self.lines)
                self.jaxpr(v, depth=depth + 1)
                sub_tokens.append(len(self.lines) - start)
                return f"<sub@{len(sub_tokens) - 1}>"

            params = ",".join(
                f"{k}={_norm_param(eqn.params[k], emit_sub)}"
                for k in sorted(eqn.params))
            for v in eqn.outvars:
                if v not in env:
                    env[v] = ("", len(env))
            outs = ",".join(self.operand(v, env) for v in eqn.outvars)
            self.lines.append(
                pre + f" {eqn.primitive.name}({ins})"
                f"[{params}] -> {outs}")
        self.lines.append(
            pre + " ret=["
            + ",".join(self.operand(v, env) for v in jj.outvars) + "]}")
        return "<jaxpr>"


def canonical_lines(closed) -> "list[str]":
    """The canonical token stream of a (Closed)Jaxpr — the exact text
    the fingerprint hashes, alpha-renaming-invariant by construction.
    Exposed for debugging and golden tests."""
    c = _Canon()
    c.jaxpr(closed)
    return c.lines


def fingerprint(closed) -> str:
    """Stable identity digest of a lowered program:
    "gfp1:<sha256-hex>".  Equal iff the canonical forms are equal —
    same structure, same literals/consts, same avals — regardless of
    variable naming or printing order."""
    h = hashlib.sha256()
    for line in canonical_lines(closed):
        h.update(line.encode())
        h.update(b"\n")
    return f"{FINGERPRINT_SCHEME}:{h.hexdigest()}"


def same_program(a, b) -> bool:
    """Canonical structural equality of two lowered programs — the ONE
    definition of "same program" bit-identity claims and CI gates
    share (replaces ad-hoc `str(jaxpr)` comparisons)."""
    return fingerprint(a) == fingerprint(b)


# ---------------------------------------------------------------------------
# structural diff
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DiffEntry:
    """The first structural divergence between two lowered programs."""

    site: str              # primitive path, e.g. "while/body_jaxpr.cond"
    index: int             # eqn index at that nesting level
    kind: str              # primitive|operands|params|outputs|
    #                        eqn-count|signature|consts
    detail: str            # human sentence naming the divergence
    phase: "str | None" = None   # enclosing protocol phase, when known
    a: str = ""            # side-A rendering of the divergent element
    b: str = ""            # side-B rendering

    def to_json(self) -> dict:
        out = dataclasses.asdict(self)
        return {k: v for k, v in out.items() if v not in (None, "")}

    def __str__(self) -> str:
        where = f"{self.site or '<top>'}[{self.index}]"
        phase = f" (phase {self.phase})" if self.phase else ""
        return f"first divergence at {where}{phase}: {self.detail}"


def _human_bytes(n: int) -> str:
    n = int(n)
    for unit, div in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n} B"


def _operand_token(v) -> str:
    if isinstance(v, Literal):
        val = v.val
        if hasattr(val, "shape") and np.asarray(val).ndim:
            return f"lit({_norm_array(val)})"
        return f"lit({getattr(val, 'item', lambda: val)()!r})"
    return _aval_token(v.aval)


def _eqn_params_token(eqn) -> str:
    # sub-jaxprs excluded: they are diffed recursively, and inlining
    # them here would blame the whole call for a leaf-level change
    return ",".join(
        f"{k}={_norm_param(eqn.params[k], lambda v: '<sub>')}"
        for k in sorted(eqn.params)
        if not (hasattr(eqn.params[k], "eqns")
                or hasattr(getattr(eqn.params[k], "jaxpr", None), "eqns")
                or (isinstance(eqn.params[k], (tuple, list))
                    and any(hasattr(x, "eqns")
                            or hasattr(getattr(x, "jaxpr", None), "eqns")
                            for x in eqn.params[k]))))


def _is_phase_cond(eqn, n_tiles) -> bool:
    if n_tiles is None or eqn.primitive.name != "cond":
        return False
    from graphite_tpu.analysis.rules import _mailbox_outputs

    return bool(_mailbox_outputs(eqn, n_tiles))


class _DiffWalker:
    def __init__(self, n_tiles, phase_names):
        self.n_tiles = n_tiles
        self.phase_names = tuple(phase_names or ())
        self.phase_seen = 0

    def _phase_label(self, k: int) -> str:
        return (self.phase_names[k] if k < len(self.phase_names)
                else f"phase_{k}")

    def invars_diff(self, ja, jb, site, phase) -> "DiffEntry | None":
        va = list(ja.constvars) + list(ja.invars)
        vb = list(jb.constvars) + list(jb.invars)
        for i in range(min(len(va), len(vb))):
            ta, tb = _aval_token(va[i].aval), _aval_token(vb[i].aval)
            if ta != tb:
                return DiffEntry(
                    site, i, "signature",
                    f"input {i} of this region changed aval "
                    f"{ta} -> {tb} "
                    f"({_human_bytes(aval_bytes(va[i].aval))} -> "
                    f"{_human_bytes(aval_bytes(vb[i].aval))})",
                    phase, ta, tb)
        if len(va) != len(vb):
            longer, side = (va, "a") if len(va) > len(vb) else (vb, "b")
            extra = longer[min(len(va), len(vb))]
            return DiffEntry(
                site, min(len(va), len(vb)), "signature",
                f"region carries {abs(len(va) - len(vb))} extra "
                f"input(s) only in program "
                f"{'A' if side == 'a' else 'B'}; first extra: "
                f"{_aval_token(extra.aval)} "
                f"({_human_bytes(aval_bytes(extra.aval))})",
                phase,
                str(len(va)), str(len(vb)))
        return None

    def walk(self, a, b, site="", phase=None) -> "DiffEntry | None":
        ja, jb = as_jaxpr(a), as_jaxpr(b)
        d = self.invars_diff(ja, jb, site, phase)
        if d is not None:
            return d
        for i in range(min(len(ja.eqns), len(jb.eqns))):
            ea, eb = ja.eqns[i], jb.eqns[i]
            here = (f"{site}.{ea.primitive.name}" if site
                    else ea.primitive.name)
            if ea.primitive.name != eb.primitive.name:
                return DiffEntry(
                    site, i, "primitive",
                    f"equation {i} is {ea.primitive.name!r} in A but "
                    f"{eb.primitive.name!r} in B", phase,
                    ea.primitive.name, eb.primitive.name)
            ops_a = [_operand_token(v) for v in ea.invars]
            ops_b = [_operand_token(v) for v in eb.invars]
            if ops_a != ops_b:
                k = next(k for k, (x, y)
                         in enumerate(zip(ops_a, ops_b)) if x != y) \
                    if len(ops_a) == len(ops_b) else min(len(ops_a),
                                                         len(ops_b))
                return DiffEntry(
                    here, i, "operands",
                    f"{ea.primitive.name} operand {k} changed: "
                    f"{ops_a[k] if k < len(ops_a) else '<absent>'} -> "
                    f"{ops_b[k] if k < len(ops_b) else '<absent>'}",
                    phase,
                    "(" + ",".join(ops_a) + ")",
                    "(" + ",".join(ops_b) + ")")
            outs_a = [_aval_token(v.aval) for v in ea.outvars]
            outs_b = [_aval_token(v.aval) for v in eb.outvars]
            if outs_a != outs_b:
                return DiffEntry(
                    here, i, "outputs",
                    f"{ea.primitive.name} outputs changed "
                    f"({','.join(outs_a)}) -> ({','.join(outs_b)})",
                    phase, ",".join(outs_a), ",".join(outs_b))
            pa, pb = _eqn_params_token(ea), _eqn_params_token(eb)
            if pa != pb:
                return DiffEntry(
                    here, i, "params",
                    f"{ea.primitive.name} params changed: {pa} -> {pb}",
                    phase, pa, pb)
            # recurse into paired sub-jaxprs, tracking phase conds
            from graphite_tpu.analysis.walk import subjaxprs

            subs_a = list(subjaxprs(ea))
            subs_b = list(subjaxprs(eb))
            inner_phase = phase
            if _is_phase_cond(ea, self.n_tiles):
                inner_phase = self._phase_label(self.phase_seen)
                self.phase_seen += 1
            if len(subs_a) != len(subs_b):
                # a sub-program count divergence IS a divergence of
                # this region's program list — report it as eqn-count
                # and attribute it to the phase the region belongs to
                # (for a phase cond, its OWN label: `phase` here is the
                # ENCLOSING phase — None at top level — which loses the
                # attribution the recursion below would have carried)
                return DiffEntry(
                    here, i, "eqn-count",
                    f"{ea.primitive.name} has {len(subs_a)} sub-"
                    f"program(s) in A but {len(subs_b)} in B",
                    inner_phase, str(len(subs_a)), str(len(subs_b)))
            for (tag, sa), (_, sb) in zip(subs_a, subs_b):
                d = self.walk(sa, sb, f"{here}/{tag}", inner_phase)
                if d is not None:
                    return d
        if len(ja.eqns) != len(jb.eqns):
            n = min(len(ja.eqns), len(jb.eqns))
            longer, label = (ja, "A") if len(ja.eqns) > len(jb.eqns) \
                else (jb, "B")
            extra = longer.eqns[n]
            out_b = sum(aval_bytes(v.aval) for v in extra.outvars)
            return DiffEntry(
                site, n, "eqn-count",
                f"program {label} has {abs(len(ja.eqns) - len(jb.eqns))}"
                f" extra equation(s) here; first extra: "
                f"{extra.primitive.name} -> ("
                + ",".join(_aval_token(v.aval) for v in extra.outvars)
                + f") ({_human_bytes(out_b)})",
                phase, str(len(ja.eqns)), str(len(jb.eqns)))
        return None


def structural_diff(a, b, *, n_tiles: "int | None" = None,
                    phase_names=()) -> "DiffEntry | None":
    """First structural divergence between two lowered programs, or
    None when they are canonically identical.

    Lockstep DFS over equations and sub-jaxprs; the first mismatch in
    primitive / operand avals+literals / output avals / normalized
    params / region signature (while-carry and branch inputs — where a
    ballooned carry shows up) is reported with its site path and, when
    `n_tiles` is given, attributed to the protocol phase whose gating
    cond encloses it (`phase_names` in phase-cond program order, the
    same convention `cost.per_phase_costs` uses).
    """
    return _DiffWalker(n_tiles, phase_names).walk(a, b)


def diff_or_none(a, b, **kw) -> "DiffEntry | None":
    """`structural_diff` guarded by the cheap hash check first."""
    if fingerprint(a) == fingerprint(b):
        return None
    d = structural_diff(a, b, **kw)
    if d is None:
        # fingerprints differ but the lockstep walk found nothing —
        # the divergence is in a normalized corner (e.g. consts); say
        # so rather than claiming identity
        return DiffEntry(
            "", 0, "consts",
            "fingerprints differ but the equation walk found no "
            "divergence — check program consts / literal tables")
    return d
