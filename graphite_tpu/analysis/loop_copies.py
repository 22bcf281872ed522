"""Whole-array `copy` instructions inside the loops of a compiled program.

A store the engine carries through its `while` loops is updated in place
only while every reader of it is a data-dependence predecessor of its one
writer (`cache_array.scatter_row`).  A reader that is not makes XLA's copy
insertion duplicate the whole store every trip, and nothing in the jaxpr
shows it: the copy exists in the OPTIMIZED program only.  This module
reads that program's text (`Simulator.compiled_text()`,
`SweepRunner.compiled_text()`, or `compiled.as_text()` of a program
compiled for a described topology) and lists the copies by loop.

`conditionals` lists the program's `conditional` instructions with the
arrays each returns: a store among them is a fresh buffer of the branch,
not the loop's carried one (a conditional's outputs are not aliased to
its operands), which is how a gate double-buffers a store.

Text only: nothing here compiles, times or runs anything.
"""

from __future__ import annotations

import dataclasses
import re

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation|branch_computations)="
    r"(?:\{([^}]*)\}|%?([\w.\-]+))")
_COPY = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\](?:\{[^}]*\})? copy\(")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_COND = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) conditional\(")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class Loop:
    """One `while` of the program, by its body computation."""

    body: str
    depth: int        # nesting depth, 1 = outermost
    comps: frozenset  # computations one trip executes: the body and what
    #                   it calls (a conditional's branches, fusions,
    #                   calls), nested `while` bodies excluded
    nested: frozenset  # bodies of the `while`s directly inside


@dataclasses.dataclass(frozen=True)
class LoopCopy:
    """One `copy` instruction that runs in a trip of a `while`."""

    name: str        # the instruction
    dtype: str       # element type as printed ("s64", "u32", ...)
    shape: tuple     # dimensions
    loop: Loop
    line: str        # the instruction as printed

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n


@dataclasses.dataclass(frozen=True)
class Conditional:
    """One `conditional` instruction (a `lax.cond` whose predicate
    reached the compiler as a scalar: a batched one is lowered to both
    branches and selects, and leaves no such instruction)."""

    name: str
    op_name: str     # the metadata's op_name ("" if none)
    outputs: tuple   # ((dtype, dims), ...) of the arrays it returns

    def returns(self, shape, dtypes=None) -> list:
        """Its outputs of `shape` (as `copies_of`: a leading batch axis
        allowed for) and one of `dtypes` (None: any)."""
        return [(d, dims) for d, dims in self.outputs
                if _is_store(dims, d, shape, dtypes)]


def _is_store(dims, dtype, shape, dtypes) -> bool:
    """`dims` END with `shape` under at most one leading (batch) axis,
    and `dtype` is one of `dtypes` (None: any)."""
    shape = tuple(shape)
    return (dims[-len(shape):] == shape and len(dims) <= len(shape) + 1
            and (dtypes is None or dtype in dtypes))


def conditionals(hlo_text: str) -> list:
    """Every `conditional` of the program, in the order printed."""
    out = []
    for line in hlo_text.splitlines():
        m = _COND.match(line)
        if not m:
            continue
        name = _OP_NAME.search(line)
        out.append(Conditional(
            name=m.group(1), op_name=name.group(1) if name else "",
            outputs=tuple(
                (a.group(1), tuple(int(x) for x in a.group(2).split(",")
                                   if x))
                for a in _ARRAY.finditer(m.group(2)))))
    return out


def computations(hlo_text: str) -> dict:
    """{computation name: its instruction lines}."""
    comps: dict = {}
    cur = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _HEADER.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        else:
            cur.append(line)
    return comps


def _called(line: str) -> list:
    out = []
    for m in _CALLED.finditer(line):
        out += [c.strip().lstrip("%")
                for c in (m.group(1) or m.group(2)).split(",")]
    return out


def loops(comps: dict) -> dict:
    """{body computation: Loop} of `computations(text)`."""
    bodies = {m.group(1) for lines in comps.values() for line in lines
              if " while(" in line for m in [_BODY.search(line)] if m}
    reached = {}
    for root in bodies:
        seen, nested, stack = set(), set(), [root]
        while stack:
            c = stack.pop()
            if c in seen:
                continue
            seen.add(c)
            for line in comps.get(c, ()):
                for callee in _called(line):
                    if callee in bodies and callee != root:
                        nested.add(callee)
                    elif callee in comps:
                        stack.append(callee)
        reached[root] = (frozenset(seen), frozenset(nested))
    depth: dict = {}

    def walk(b, d):
        depth[b] = max(depth.get(b, 0), d)
        for n in reached[b][1]:
            walk(n, d + 1)

    inner = {n for _, nested in reached.values() for n in nested}
    for b in bodies - inner:
        walk(b, 1)
    return {b: Loop(body=b, depth=depth[b], comps=reached[b][0],
                    nested=reached[b][1]) for b in bodies}


def loop_copies(hlo_text: str, min_size: int = 1, under: str = "") -> list:
    """Every `copy` of at least `min_size` elements that a trip of some
    `while` executes, outermost loops first.

    `under`: a piece of an `op_name` (a scope of `obs/scopes.py`, e.g.
    "gt.mem.requester/").  Only the DEEPEST loop that executes an
    instruction so named, and the loops nested in it, are searched — with
    the requester's scope that is the engine's iteration body, whatever
    encloses it (quantum loop, inner block, a campaign's batch)."""
    comps = computations(hlo_text)
    all_loops = loops(comps)
    keep = set(all_loops)
    if under:
        named = [lp for lp in all_loops.values()
                 if any(under in line for c in lp.comps
                        for line in comps.get(c, ()))]
        if not named:
            raise ValueError(f"no loop executes an op named {under!r}")
        top = max(named, key=lambda lp: lp.depth)
        keep, stack = set(), [top.body]
        while stack:
            b = stack.pop()
            if b not in keep:
                keep.add(b)
                stack += all_loops[b].nested
    out = []
    for lp in sorted(all_loops.values(), key=lambda x: (x.depth, x.body)):
        if lp.body not in keep:
            continue
        for c in sorted(lp.comps):
            for line in comps.get(c, ()):
                m = _COPY.match(line)
                if not m:
                    continue
                shape = tuple(int(x) for x in m.group(3).split(",") if x)
                cp = LoopCopy(name=m.group(1), dtype=m.group(2),
                              shape=shape, loop=lp, line=line.strip())
                if cp.size >= min_size:
                    out.append(cp)
    return out


def copies_of(copies, shape, dtypes=None) -> list:
    """The copies whose dimensions END with `shape` (a leading batch
    axis of a campaign program is allowed for) and whose element type is
    one of `dtypes` (None: any).  A 64-bit store is two 32-bit halves on
    the TPU: ask for ("s64", "u32") there."""
    return [c for c in copies if _is_store(c.shape, c.dtype, shape, dtypes)]
