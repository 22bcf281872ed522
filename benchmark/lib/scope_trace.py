"""Device time by the program's own scope names, and the drive loop's spans.

The program names its layers on the device (`graphite_tpu/obs/scopes.py`:
`gt.fetch`, `gt.net.mailbox`, `gt.mem.home_start`, ...) and spans its
drive loop on the host (`Simulator.attach_tracer`: `run` > `dispatch` >
`wait` > `fetch` > `results`, each also a `gt:<name>` TraceAnnotation).
`get(ctx)` takes ONE more traced slice per `--trace 1` run, with a tracer
attached, and reduces it to

    {"scoped": bool,              # False: the executable carries no scope
     "busy_s": {scope: s},        # leaf operations of "XLA Ops" inside the
     "ops": {scope: n},           #   `bench:run` window, by scope
     "busy_total_s": s, "leaf_events": n, "window_s": s,
     "top": [[label, s], ...],    # ten longest, as `scope · kind · shape`
     "unscoped_kinds": [[kind, s, n], ...],   # what the unscoped ones are
     "gaps": {"gt:<span>": s},    # no program on the device, by the
                                  #   innermost gt:* span the host was in
     "spans": [row, ...],         # the tracer's spans (Tracer.to_rows)
     "iterations": n}

and prints the whole table.  It returns None where the program has
neither scopes nor a tracer (a commit from before they existed).

**Where a scope comes from.**  The installed profiler's TPU trace (jax
0.9.0, `enable_hlo_proto=False`) carries no `op_name`: an "XLA Ops" event
has its HLO text as name and three timing stats, and the device plane has
no name-scope line.  So the scope is read from the compiled program:
`Simulator.compiled_text()` gives every instruction's
`metadata={op_name="jit(run_<tag>)/gt.quantum/while/body/gt.core/..."}`, an
event's name starts with `%<instruction> = `, and
`graphite_tpu.obs.scopes.deepest` picks the innermost registered name of
the path.  A fusion spans scopes; it counts for the scope of its own
`op_name` (its root).  An instruction XLA made itself has no `op_name`: a
fusion or async start then takes its called computation's (the root's,
else the last instruction's that has one) and an async `*-done` its
start's (`op_names`); what wraps nothing of the program (copies on the
loop carry, `copy-start`/`copy-done`, relayouts) stays unscoped.

**The cache trap.**  JAX's persistent-cache key ignores locations, and a
named scope is a location: an executable compiled before the scopes
existed is served for the scoped program and names nothing.  Then `get`
prints one line saying so and `busy_s` is None: every scope metric is
left out of the line.  The drive loop's programs carry a tag of the scope
registry in their name, so the key moves when a name is added
(`obs/scopes.py: CACHE_TAG`); after MOVING a scope, measure from an empty
compile cache.

**A later metric** reads a scope in three lines (`layer_metrics/
net_busy_share.py` is the pattern):

    from lib import scope_trace
    def read(ctx):
        return scope_trace.share(ctx, lambda s: s.startswith("gt.net."))

and a span with `scope_trace.span_ms(ctx, "dispatch")`.
"""

import re
import shutil

from . import paths, profile, xplane
from .xplane import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, WINDOW_SPAN,
                     _clip, leaves, union_length)

GT_PREFIX = "gt:"
UNSCOPED = "unscoped"
# the memory engine's scopes outside its six gated phases
MEM_UNGATED = ("gt.mem.base", "gt.mem.stage_flush")
OUTSIDE = "outside gt:*"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_DONE = re.compile(r"-done\(.*?%([\w.\-]+)\)")
_KEY = "scope_trace"


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of an optimized HLO module's text.
    An instruction XLA made itself has no `op_name`; where it wraps
    instructions that have one it inherits theirs: a fusion or async
    start takes its called computation's (the root's, else the last
    instruction's that has one), an async `*-done` its start's."""
    out, last_in, root_in, pending = {}, {}, {}, []
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c else (None if line.startswith("}")
                                         else comp)
            continue
        n = _OP_NAME.search(line)
        if n:
            out[m.group(1)] = n.group(1)
            last_in[comp] = n.group(1)
            if line.lstrip().startswith("ROOT"):
                root_in[comp] = n.group(1)
        else:
            pending.append((m.group(1), line))
    for name, line in pending:
        calls = _CALLS.search(line)
        done = _DONE.search(line)
        if calls:
            got = root_in.get(calls.group(1)) or last_in.get(calls.group(1))
        elif done:
            got = out.get(done.group(1))
        else:
            got = None
        if got:
            out[name] = got
    return out


def _instruction(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def _kind_shape(event_name: str) -> list:
    """[kind, shape] of an event (either may be missing): with its scope,
    what a later trace of a recompiled program finds again, unlike the
    instruction's number."""
    return xplane.short_name(event_name).split(" ")[1:3]


def _innermost(t, spans) -> str:
    inner = None
    for name, s, d in spans:
        if s <= t < s + d and (inner is None or d < inner[2]):
            inner = (name, s, d)
    return inner[0] if inner else OUTSIDE


def _subtract(w0, w1, intervals):
    """[w0, w1) minus the union of (start, end) intervals."""
    out, edge = [], w0
    for s, e in sorted(intervals):
        if s > edge:
            out.append((edge, min(s, w1)))
        edge = max(edge, e)
        if edge >= w1:
            break
    if edge < w1:
        out.append((edge, w1))
    return [(a, b) for a, b in out if b > a]


def reduce(trace: dict, names: dict, deepest, iterations: int = 0) -> dict:
    """The numbers of one traced window (see the module's docstring).
    `trace` is `lib/xplane.py`'s neutral structure with the host's `gt:*`
    events kept; `names` maps instruction -> op_name; `deepest(op_name)`
    gives the innermost registered scope or None."""
    devices, bench, gt = {}, [], []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            evs = [tuple(e) for e in line["events"]]
            if m:
                devices.setdefault(int(m.group(1)), {})[line["name"]] = evs
            else:
                bench += [e for e in evs if e[0] == WINDOW_SPAN]
                gt += [e for e in evs if e[0].startswith(GT_PREFIX)]
    devices = {k: v for k, v in devices.items() if v.get(OPS_LINE)}
    if not devices or not bench:
        raise ValueError("the trace holds no TPU operation or no "
                         f"{WINDOW_SPAN} span")
    w0 = min(s for _, s, _ in bench)
    w1 = max(s + d for _, s, d in bench)
    scope_of = {k: deepest(v) for k, v in names.items()}
    scoped = any(scope_of.values())

    cuts = sorted({t for _, s, d in gt for t in (s, s + d) if w0 < t < w1})
    busy, count, label_time, gaps, kinds = {}, {}, {}, {}, {}
    n_events, busy_union = 0, 0
    for dev in sorted(devices):
        ops = _clip(leaves(devices[dev][OPS_LINE]), w0, w1)
        n_events += len(ops)
        busy_union += union_length((s, s + d) for _, s, d in ops)
        for name, _, d in ops:
            scope = scope_of.get(_instruction(name)) or UNSCOPED
            busy[scope] = busy.get(scope, 0) + d
            count[scope] = count.get(scope, 0) + 1
            kind_shape = _kind_shape(name)
            label = " · ".join([scope] + kind_shape)
            label_time[label] = label_time.get(label, 0) + d
            if scope == UNSCOPED:
                s_n = kinds.setdefault((kind_shape or ["?"])[0], [0, 0])
                s_n[0] += d
                s_n[1] += 1
        mods = _clip(devices[dev].get(MODULES_LINE, []), w0, w1)
        for a, b in _subtract(w0, w1, [(s, s + d) for _, s, d in mods]):
            edges = [a] + [t for t in cuts if a < t < b] + [b]
            for p, q in zip(edges, edges[1:]):
                where = _innermost((p + q) // 2, gt)
                gaps[where] = gaps.get(where, 0) + (q - p)
    total = sum(busy.values())
    overlap = abs(total - busy_union) > 0.01 * max(total, 1)
    if overlap:
        # leaves that overlap cannot be shared out: say so, name nothing
        print(f"scope_trace: leaf operations overlap (durations sum to "
              f"{total} ns, their union is {busy_union} ns); scope "
              f"metrics are left out")
    usable = scoped and not overlap
    n = len(devices)
    return {
        "scoped": scoped,
        "busy_s": {k: v / n / 1e9 for k, v in busy.items()}
        if usable else None,
        "ops": count if usable else None,
        "busy_total_s": total / n / 1e9,
        "leaf_events": n_events,
        "window_s": (w1 - w0) / 1e9,
        "top": [[k, v / n / 1e9] for k, v in sorted(
            label_time.items(), key=lambda kv: -kv[1])[:10]]
        if usable else None,
        "unscoped_kinds": [[k, v[0] / n / 1e9, v[1]] for k, v in sorted(
            kinds.items(), key=lambda kv: -kv[1][0])[:6]]
        if usable else None,
        "gaps": {k: v / n / 1e9 for k, v in gaps.items()},
        "iterations": iterations,
    }


def shares(red: dict) -> "dict | None":
    """{scope: % of device busy}, summing to 100; None without scopes."""
    if red is None or not red["busy_s"]:
        return None
    total = sum(red["busy_s"].values())
    out = {k: 100.0 * v / total for k, v in red["busy_s"].items()}
    if abs(sum(out.values()) - 100.0) > 1e-6:
        raise AssertionError(f"scope shares sum to {sum(out.values())}")
    return out


def table(red: dict) -> list:
    """The printed lines: `scope <name> <s> <% of busy> <ops/iteration>`,
    the ten longest operations, `unscoped <kind> <s> <ops/iteration>`,
    `gap <gt:span> <s>`."""
    out = []
    sh = shares(red)
    if sh is not None:
        its = max(1, red["iterations"])
        for k in sorted(sh, key=lambda k: -sh[k]):
            out.append(f"scope {k} {red['busy_s'][k]:.6f} {sh[k]:.2f} "
                       f"{red['ops'][k] / its:.1f}")
        for label, s in red["top"]:
            out.append(f"top {s:.6f} {label}")
        for kind, s, n_ops in red["unscoped_kinds"]:
            out.append(f"unscoped {kind} {s:.6f} {n_ops / its:.1f}")
    for k, v in sorted(red["gaps"].items(), key=lambda kv: -kv[1]):
        out.append(f"gap {k} {v:.6f}")
    return out


def load(trace_dir: str) -> dict:
    """The newest .xplane.pb under `trace_dir` as the neutral structure:
    the device's operations and programs, the host's `bench:run` and
    `gt:*` annotations."""
    _, data = xplane._newest(trace_dir)
    planes = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [[e.name, e.start_ns, e.duration_ns] for e in line.events
                   if on_device or e.name == WINDOW_SPAN
                   or e.name.startswith(GT_PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _take(ctx) -> "dict | None":
    sim = ctx.own.get("sim")
    try:
        from graphite_tpu.obs import scopes
        from graphite_tpu.obs.trace import Tracer
    except ImportError:
        return None
    if sim is None or not hasattr(sim, "attach_tracer") \
            or not hasattr(sim, "compiled_text"):
        return None
    names = op_names(sim.compiled_text())
    driver = paths.load_module("drivers", ctx.traffic["driver"])
    tracer = Tracer()
    sim.attach_tracer(tracer)
    try:
        # lib.profile.tracing() keeps its trace until the next one is taken
        driver.traced_slice(ctx, profile.tracing)
        red = reduce(load(profile.TRACE_DIR), names, scopes.deepest,
                     int(sim.last_n_iterations))
    except ValueError as e:
        # nothing of a TPU in the trace: the spans are still the program's
        print(f"scope_trace: {e}; scope metrics are left out")
        red = {"scoped": False, "busy_s": None, "gaps": {}}
    else:
        if not red["scoped"]:
            print("scope_trace: the executable names no registered scope "
                  "(it was compiled before the scopes existed and served "
                  "from the compile cache); scope metrics are left out")
        print(f"scope trace: {red['leaf_events']} leaf operations, busy "
              f"{red['busy_total_s']:.6f} s of {red['window_s']:.6f} s, "
              f"{red['iterations']} iterations")
        for line in table(red):
            print(line)
    finally:
        sim.attach_tracer(None)
        shutil.rmtree(profile.TRACE_DIR, ignore_errors=True)
    # the traced call's own trace: a host-driven slice is preceded by an
    # untraced run_chunk() that the tracer saw too
    rows = tracer.to_rows()
    red["spans"] = [r for r in rows if r["trace"] == rows[-1]["trace"]]
    return red


def get(ctx) -> "dict | None":
    """The reduction, taken once per process."""
    if _KEY not in ctx.own:
        ctx.own[_KEY] = _take(ctx)
    return ctx.own[_KEY]


def share(ctx, belongs) -> "float | None":
    """% of device busy time in the scopes `belongs(name)` accepts."""
    sh = shares(get(ctx))
    if sh is None:
        return None
    return sum(v for k, v in sh.items() if belongs(k))


def span_ms(ctx, *names: str, mean: bool = False) -> "float | None":
    """Summed (or mean) duration in ms of the traced slice's spans with
    one of `names`; None where the program records none."""
    red = get(ctx)
    if red is None:
        return None
    durs = [r["dur_us"] for r in red["spans"] if r["span"] in names]
    if not durs:
        return None
    return sum(durs) / (len(durs) if mean else 1) / 1e3
