"""From a profiler trace to numbers: device busy time, idle share, the
operations that took most time, program launches, and idle gaps by what
the host was doing.

The reduction works on a neutral structure, so that it can be checked
against a small recorded trace (`lib/recorded_trace.json`) without JAX:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]},
                           ...]}, ...]}

On a TPU the device plane's "XLA Modules" line has one event per program
launched and its "XLA Ops" line one per operation executed.  Control flow
nests: a `while` event spans its whole loop and encloses the events of its
body, so busy time is the union of the LEAF events only.  What is left of
a program's span is the device between operations (loop control, waits);
what is left of the window outside any program is the device waiting for
the host.  Host spans (`lib.clock.Spans`, "bench:<name>") are
TraceAnnotations on the host plane, on the same clock.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "run"


def _newest(trace_dir: str):
    """(path, ProfileData) of the newest .xplane.pb under `trace_dir`."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1], jax.profiler.ProfileData.from_file(files[-1])


def load(trace_dir: str) -> dict:
    """The newest .xplane.pb under `trace_dir`, as the neutral structure,
    keeping only the lines the reduction reads."""
    _, data = _newest(trace_dir)
    planes = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if on_device:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events]
            else:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


_KIND = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(hlo: str) -> str:
    """'%copy.460 = u32[64,1024,16]{2,1,0:T(8,128)S(1)} copy(...)' ->
    'copy.460 copy u32[64,1024,16]': the instruction's own name (what a
    later trace of the same program finds again), its kind, and its first
    result shape."""
    name, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    kind, shape = _KIND.search(rhs), _SHAPE.search(rhs)
    calls = re.search(r"calls=%([\w.\-]+)", rhs)
    out = [name.lstrip("%")]
    if kind:
        out.append(kind.group(1))
    if shape:
        out.append(shape.group(0))
    if calls and kind and kind.group(1) == "fusion":
        out.append("calls=" + calls.group(1)[:40])
    return " ".join(out)[:120]


def union_length(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def leaves(events: list) -> list:
    """The events that enclose no other event (sorted by start).  An
    event encloses the next one in start order iff that one starts before
    it ends."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, d) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][1] < s + d and d > 0 \
                and evs[i + 1][1] + evs[i + 1][2] <= s + d:
            continue
        out.append((name, s, d))
    return out


def _clip(events, w0, w1):
    out = []
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((name, a, b - a))
    return out


def _label_at(t, modules, spans) -> str:
    for name, s, d in modules:
        if s <= t < s + d:
            return f"device, inside program {name} (between operations)"
    inner = None
    for name, s, d in spans:
        if s <= t < s + d and (inner is None or d < inner[2]):
            inner = (name, s, d)
    if inner:
        return f"no program on the device; host in {inner[0]}"
    return "no program on the device; host outside the benchmark's spans"


def reduce(trace: dict) -> dict:
    """The numbers of one traced window.  The window is the union span of
    the host's `bench:run` annotations; without one, the device events'
    own extent."""
    devices, spans = {}, []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            if m:
                devices.setdefault(int(m.group(1)), {})[line["name"]] = \
                    [tuple(e) for e in line["events"]]
            else:
                spans += [tuple(e) for e in line["events"]
                          if e[0].startswith(SPAN_PREFIX)]
    devices = {k: v for k, v in devices.items() if v.get(OPS_LINE)}
    if not devices:
        raise ValueError("the trace holds no operation on a TPU device")
    runs = [e for e in spans if e[0] == WINDOW_SPAN]
    if runs:
        w0 = min(s for _, s, _ in runs)
        w1 = max(s + d for _, s, d in runs)
    else:
        every = [e for v in devices.values() for e in v[OPS_LINE]]
        w0 = min(s for _, s, _ in every)
        w1 = max(s + d for _, s, d in every)
    window = w1 - w0

    busy, in_program, launches = [], [], []
    op_time, gap_time = {}, {}
    n_events = 0
    for dev in sorted(devices):
        ops = _clip(leaves(devices[dev][OPS_LINE]), w0, w1)
        mods = _clip(devices[dev].get(MODULES_LINE, []), w0, w1)
        n_events += len(ops)
        busy.append(union_length((s, s + d) for _, s, d in ops))
        in_program.append(union_length((s, s + d) for _, s, d in mods))
        launches.append(sum(1 for _, s, _ in devices[dev].get(
            MODULES_LINE, []) if w0 <= s < w1))
        for name, _, d in ops:
            op_time[name] = op_time.get(name, 0) + d
        # idle gaps: between consecutive leaf operations, and at the ends
        mods_sorted = sorted(mods, key=lambda e: e[1])
        edge = w0
        for name, s, d in ops:
            if s > edge:
                label = _label_at((edge + s) // 2, mods_sorted, spans)
                gap_time[label] = gap_time.get(label, 0) + (s - edge)
            edge = max(edge, s + d)
        if w1 > edge:
            label = _label_at((edge + w1) // 2, mods_sorted, spans)
            gap_time[label] = gap_time.get(label, 0) + (w1 - edge)
    n = len(devices)
    busy_ns = sum(busy) / n
    if busy_ns > window:
        raise AssertionError(
            f"busy {busy_ns} ns over a window of {window} ns: the reducer "
            f"counts an interval twice")

    def top(d):
        return [[short_name(k), v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": window / 1e9,
        "busy_s": busy_ns / 1e9,
        "in_program_s": sum(in_program) / n / 1e9,
        "idle_share": 1.0 - busy_ns / window,
        "launches": sum(launches) / n,
        "devices": n,
        "leaf_events": n_events,
        "breakdown": {"device_ops": top(op_time),
                      "idle_gaps": top(gap_time)},
    }


def describe(trace_dir: str) -> str:
    """Planes, lines, event counts and first names of a trace: what to
    look at by hand before trusting the reduction."""
    path, data = _newest(trace_dir)
    out = [f"{path}: {os.path.getsize(path)} bytes"]
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:4]:
                out.append(f"      {e}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
