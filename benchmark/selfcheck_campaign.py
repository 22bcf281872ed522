"""The served cell checks itself.  CPU, 16 tiles, seconds; prints no
contract line.  (`selfcheck.py` covers the reducers, the digest, the
loaders and the solo driver; this file covers `drivers/campaign_closed.py`
and what it reads.)

    JAX_PLATFORMS=cpu python benchmark/selfcheck_campaign.py

run.py end to end over `CampaignService` on the self-check's 16-tile
stand-in (`selfcheck_data/`: `tiny-16-campaign`, `campaign-closed-tiny`,
its 16 stored digests), with the look for a chip stubbed here and only
here, and ONE service kept for all the runs of this process (each run
would otherwise trace and load the same program again, 10 s a time):
- a sound run is correct, and its traced run reports the service's five
  metrics, `compile_s` and - through the program handle's tracer and
  counter - `run_dispatch_ms`, `run_fetch_ms` and `dispatches_counted`,
  no `device_idle_share` (the slice is a short batch: its idle share is
  not the window's), and compiles nothing in its traced slices;
- a program without the handle (`CampaignService.resident_program`
  taken away, as on a commit from before it) yields no scope metric and
  none of the handle's three, and still compiles and lowers nothing;
- an altered stored digest, an envelope dropped on its way out of
  `drain()` and an envelope drained twice each come out NOT correct;
- so does the control (the configuration's `core: simple`).
"""

import contextlib
import copy
import io
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from lib import paths, profile, target, xplane  # noqa: E402

CELL = "selfcheck-campaign-16"
REAL_CELL = "campaign64-dram"
CONFIG = "tiny-16-campaign"
SERVICE_METRICS = {"jobs_per_s", "batch_occupancy", "batch_host_ms",
                   "batch_execute_ms", "served_iters_per_batch"}
# what the program handle's hooks feed: its tracer's spans, its counter
HANDLE_METRICS = {"run_dispatch_ms", "run_fetch_ms", "dispatches_counted"}
SCOPE_METRICS = {"core_busy_share", "net_busy_share", "sync_busy_share",
                 "mem_phase_busy_share", "mem_ungated_busy_share",
                 "unscoped_busy_share"}


def check(ok, what):
    if not ok:
        raise SystemExit(f"selfcheck_campaign: FAILED: {what}")


def tiny_manifest():
    m = copy.deepcopy(REAL_MANIFEST)
    m["configs"].append({"name": CONFIG})
    m["workloads"].append({"name": CELL, "config": CONFIG,
                           "traffic": "campaign-closed-tiny", "chips": 1})
    for x in m["end_to_end"] + m["per_layer"]:
        if REAL_CELL in x.get("workloads", ()):
            x["workloads"].append(CELL)
    return m


def load_json_too(*parts):
    """The real files first, then the self-check's stand-ins."""
    try:
        return REAL_LOAD_JSON(*parts)
    except FileNotFoundError:
        with open(os.path.join(HERE, "selfcheck_data", *parts)) as f:
            return json.load(f)


class Compiles:
    """Programs compiled (or loaded) while a traced slice was taken:
    `lib.profile.tracing` wrapped, the one door every slice goes through."""

    def __init__(self):
        import jax

        self.events, self.in_slices, self.slices = 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        self.events += name == run.COMPILE_EVENT

    @contextlib.contextmanager
    def tracing(self):
        before = self.events
        with REAL_TRACING():
            yield
        self.slices += 1
        self.in_slices += self.events - before


def one_service(**kw):
    """The driver's `CampaignService(...)`: made once, then kept."""
    if "svc" not in one_service.__dict__:
        one_service.svc = CampaignService(**kw)
    return one_service.svc


def drive(main, trace=None, seconds="1"):
    """(exit code, the contract line or None, all output)."""
    buf = io.StringIO()
    argv = ["--workload", CELL, "--seed", "2147483659", "--seconds", seconds]
    if trace is not None:
        argv += ["--trace", str(trace)]
    with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
        rc = main(argv)
    lines = buf.getvalue().strip().splitlines()
    doc = None
    if main is run.main and lines and lines[-1].startswith('{"correct"'):
        doc = json.loads(lines[-1])
    return rc, doc, buf.getvalue()


def traced_run(compiles):
    """A `--trace 1` run whose first reduction is fed from the recorded
    trace (a CPU trace has no device plane)."""
    with open(os.path.join(HERE, "lib", "recorded_trace.json")) as f:
        rec = json.load(f)["trace"]
    real_reduce = profile.reduce_last
    profile.reduce_last = lambda: xplane.reduce(rec)
    before = compiles.in_slices, compiles.slices
    try:
        rc, doc, out = drive(run.main, 1)
    finally:
        profile.reduce_last = real_reduce
    return rc, doc, out, (compiles.in_slices - before[0],
                          compiles.slices - before[1])


def check_sound(compiles):
    rc, doc, out = drive(run.main, 0)
    check(rc == 0 and doc and doc["correct"] is True and doc["failed"] == 0
          and doc["attempted"] >= 8, f"a sound run: rc {rc}\n{out}")
    check(set(doc["metrics"]) == {"sim_records_per_s", "peak_hbm_gb",
                                  "setup_s"}, f"the line's keys: {doc}")
    check("(limit 0)" in out and "compared" in out,
          "numbers are not printed beside their limits")

    rc, doc, out, (n_compiled, n_slices) = traced_run(compiles)
    got = set(doc["metrics"]) if doc else set()
    check(rc == 0 and doc["correct"] is True
          and SERVICE_METRICS | HANDLE_METRICS | {"compile_s"} <= got
          and "device_idle_share" not in got,
          f"a traced run: rc {rc}, metrics {sorted(got)}\n{out}")
    check(n_slices == 2 and n_compiled == 0,
          f"{n_slices} traced slices compiled {n_compiled} programs")
    check(doc["metrics"]["batch_occupancy"]["value"] == 100.0,
          f"occupancy {doc['metrics']['batch_occupancy']}")
    return 2


def check_without_handle(compiles):
    real = CampaignService.resident_program
    del CampaignService.resident_program
    try:
        rc, doc, out, (n_compiled, n_slices) = traced_run(compiles)
    finally:
        CampaignService.resident_program = real
    got = set(doc["metrics"]) if doc else set()
    check(rc == 0 and doc["correct"] is True and SERVICE_METRICS <= got
          and not got & (SCOPE_METRICS | HANDLE_METRICS),
          f"a program without the handle: rc {rc}, {sorted(got)}\n{out}")
    check(n_slices == 1 and n_compiled == 0 and "no scope metric" in out,
          f"without the handle: {n_slices} slices, {n_compiled} compiled")
    return 1


def check_broken():
    real_reference = target.load_reference

    def altered(name):
        ref = copy.deepcopy(real_reference(name))
        key = sorted(ref["jobs"])[0]
        ref["jobs"][key] = ref["jobs"][key][::-1]
        return ref

    target.load_reference = altered
    try:
        rc, doc, out = drive(run.main, 0)
    finally:
        target.load_reference = real_reference
    # the altered key is stream 0's, one of the run's two
    check(rc == 0 and doc["correct"] is False and doc["failed"] >= 1
          and "differs from the stored digest" in out,
          f"an altered digest: correct came out true\n{out}")

    real_drain = CampaignService.drain

    def tamper(what):
        def drain(self, **kw):
            for env in real_drain(self, **kw):
                drain.n += 1
                if drain.n == 3:            # the window's third envelope
                    if what == "drop":
                        continue
                    yield env
                yield env
        drain.n = -8                        # set-up's grid goes first
        return drain

    for what, line in (("drop", "did not come back: 1"),
                       ("twice", "more than once: 1")):
        CampaignService.drain = tamper(what)
        try:
            rc, doc, out = drive(run.main, 0)
        finally:
            CampaignService.drain = real_drain
        check(rc == 0 and doc["correct"] is False and line in out,
              f"an envelope {what}: correct came out true\n{out}")

    rc, doc, out = drive(control.main)
    check(rc == 0 and "differs from the stored digest" in out,
          f"the control came out correct\n{out}")
    return 4


if __name__ == "__main__":
    REAL_MANIFEST = paths.load_manifest()
    REAL_LOAD_JSON = paths.load_json
    REAL_TRACING = profile.tracing
    paths.load_json = load_json_too
    paths.load_manifest = tiny_manifest
    import control  # noqa: E402
    import run  # noqa: E402
    from graphite_tpu.serve import service  # noqa: E402
    from graphite_tpu.serve.service import CampaignService  # noqa: E402

    service.CampaignService = one_service
    run.check_device = lambda n, peaks: None
    compiles = Compiles()
    profile.tracing = compiles.tracing
    n = check_sound(compiles) + check_without_handle(compiles) \
        + check_broken()
    print(f"selfcheck_campaign: ok ({n} checks)")
