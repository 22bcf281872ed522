"""What a served cell's per-layer metrics read: the window's jobs (the
driver's own records, `drivers/campaign_closed.py`), the service's batch
log, and the service tracer's spans of the window's batches and jobs.
Each reader gives None where the program records nothing of the kind."""


def ok_jobs(ctx) -> list:
    """The window's job records with exactly one ok envelope."""
    return [j for g in ctx.readings for j in g.get("jobs", ())
            if len(j["envelopes"]) == 1
            and j["envelopes"][0].status == "ok"]


def batch_ids(ctx) -> list:
    """Ids of the batches that served the window's jobs, in order."""
    return sorted({j["envelopes"][0].batch_id for j in ok_jobs(ctx)})


def batch_reports(ctx) -> list:
    """The service's `BatchReport`s of the window's batches."""
    ids = set(batch_ids(ctx))
    log = getattr(ctx.own.get("svc"), "batch_log", ())
    return [b for b in log if b.batch_id in ids]


def spans(ctx, name: str, trace_ids) -> list:
    """Durations in seconds of the tracer's spans called `name` in the
    given traces; [] where the service keeps no tracer or no such span."""
    tracer = getattr(ctx.own.get("svc"), "tracer", None)
    if tracer is None:
        return []
    ids = set(trace_ids)
    return [s.dur_s for s in tracer.spans
            if s.name == name and s.trace_id in ids]


def batch_span_ms(ctx, *names: str) -> "float | None":
    """Mean over the window's batches of the summed spans `names` of a
    batch's trace, in ms; None unless every batch has every one."""
    ids = [f"batch-{b}" for b in batch_ids(ctx)]
    total = 0.0
    for name in names:
        durs = spans(ctx, name, ids)
        if not ids or len(durs) != len(ids):
            return None
        total += sum(durs)
    return 1e3 * total / len(ids)
