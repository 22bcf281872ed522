"""Set-up, by the program's own spans: what every `entry` metric reads.

The program times its set-up where the work happens
(`graphite_tpu/obs/trace.py`: `SETUP_SPANS` — `import`, `build_trace`,
`construct` > `init_state` / `encode_trace` / `place`, `warmup` >
`first_dispatch`) and its program ledger hangs JAX's own events under
them (`jax_trace`, `jax_lower`, `jax_compile` with `cache_hit`).  With no
tracer given they are kept in the process-wide `obs.trace.SETUP`, on
`time.perf_counter`, which is `lib.clock.now`; a served cell's batches
carry theirs in the service's tracer, under `batch-<n>`.

`get(ctx)` reduces them once per process to

    {"exclusive_s": {label: s},   # every instant of set-up under its
     "count": {label: n},         #   INNERMOST span: the labels part the
     "inclusive_s": {label: s},   #   traced time, they sum to `traced_s`
     "traced_s": s}               # the union of every set-up span

and prints `setup-trace <label> <exclusive s> <count> <inclusive s>`,
longest first, the five longest compiles or loads (`setup-trace program <s>
<loaded|compiled> <fun_name> (in <parent span>)`: which program the time
went to), then the cold / warm verdict.  A label is a span's name;
`jax_compile` parts in two, `jax_compile.loaded` (a persistent-cache hit:
an executable read back) and `jax_compile.compiled` (a backend compile).
"In set-up" is every span that ended before the window's first reading
started, and, in a served cell, every span (but the reconstructed row
`batch`) of the batches numbered below the window's first.  The two
tracers may read different clocks, so each is reduced alone and the two
are added: a worker's batch and the main thread's spans do not overlap.

`get` returns None where the program records no such span (a commit from
before they existed): every metric over it is then left out.
"""

from . import served

LOADED = "jax_compile.loaded"
COMPILED = "jax_compile.compiled"
_KEY = "setup_trace"


def label(span) -> str:
    if span.name != "jax_compile":
        return span.name
    return LOADED if span.attrs.get("cache_hit") else COMPILED


def part(spans) -> "tuple[dict, float]":
    """({label: seconds under it as the innermost span}, union seconds)
    of spans on ONE clock.  The innermost of the spans open at an instant
    is the one that started last."""
    edges = sorted({t for s in spans for t in (s.t_start, s.t_end)})
    starts = sorted(spans, key=lambda s: s.t_start)
    out, open_, i = {}, [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(starts) and starts[i].t_start <= a:
            open_.append(starts[i])
            i += 1
        open_ = [s for s in open_ if s.t_end > a]
        if open_:
            inner = label(max(open_, key=lambda s: s.t_start))
            out[inner] = out.get(inner, 0.0) + (b - a)
    return out, sum(out.values())


def _in_setup(ctx) -> "list[list] | None":
    """The set-up spans, one list a tracer; None without `obs.trace.SETUP`."""
    try:
        from graphite_tpu.obs import trace as obs_trace
    except ImportError:
        return None
    setup = getattr(obs_trace, "SETUP", None)
    if setup is None or not ctx.readings:
        return None
    t_window = ctx.readings[0]["t0"]
    groups = [[s for s in setup.spans if s.t_end <= t_window]]
    tracer = getattr(ctx.own.get("svc"), "tracer", None)
    ids = served.batch_ids(ctx)
    if tracer is not None and ids:
        before = {f"batch-{b}" for b in range(min(ids))}
        groups.append([s for s in tracer.spans
                       if s.trace_id in before and s.name != "batch"])
    return groups


def _take(ctx) -> "dict | None":
    groups = _in_setup(ctx)
    if groups is None or not any(groups):
        return None
    red = {"exclusive_s": {}, "count": {}, "inclusive_s": {},
           "traced_s": 0.0}
    slowest = 0.0
    for spans in groups:
        exclusive, union = part(spans)
        red["traced_s"] += union
        for k, v in exclusive.items():
            red["exclusive_s"][k] = red["exclusive_s"].get(k, 0.0) + v
        for s in spans:
            k = label(s)
            red["count"][k] = red["count"].get(k, 0) + 1
            red["inclusive_s"][k] = red["inclusive_s"].get(k, 0.0) + s.dur_s
            if k == COMPILED:
                slowest = max(slowest, s.dur_s)
    for k in sorted(red["count"], key=lambda k: -red["exclusive_s"].get(k, 0)):
        print(f"setup-trace {k} {red['exclusive_s'].get(k, 0.0):.6f} "
              f"{red['count'][k]} {red['inclusive_s'][k]:.6f}")
    programs = sorted((s for spans in groups for s in spans
                       if s.name == "jax_compile"), key=lambda s: -s.dur_s)
    for s in programs[:5]:
        print(f"setup-trace program {s.dur_s:.6f} "
              f"{label(s).split('.')[1]} {s.attrs.get('fun_name')} "
              f"(in {s.attrs.get('parent')})")
    import jax

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    verdict = (f"COLD: a program the persistent cache keeps (a compile of "
               f"{floor:g} s or more) was compiled" if slowest >= floor
               else f"warm: every compile was under the persistent "
                    f"cache's minimum of {floor:g} s")
    print(f"setup-trace traced {red['traced_s']:.6f} s in "
          f"{sum(red['count'].values())} spans; programs_compiled "
          f"{red['count'].get(COMPILED, 0)} "
          f"({red['inclusive_s'].get(COMPILED, 0.0):.6f} s, the slowest "
          f"{slowest:.6f} s), programs_loaded {red['count'].get(LOADED, 0)} "
          f"({red['inclusive_s'].get(LOADED, 0.0):.6f} s); {verdict}")
    return red


def get(ctx) -> "dict | None":
    """The reduction, taken and printed once per process."""
    if _KEY not in ctx.own:
        ctx.own[_KEY] = _take(ctx)
    return ctx.own[_KEY]


def seconds(ctx, *labels: str) -> "float | None":
    """Summed exclusive seconds of `labels` in set-up (0.0 where the
    program records such spans and made none: a cold run loads nothing);
    None where the program records no set-up span."""
    red = get(ctx)
    if red is None:
        return None
    return sum(red["exclusive_s"].get(k, 0.0) for k in labels)
