"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: loads the cell (BENCHMARK.json -> configs/, traffic/,
drivers/, references/), sets up, measures for `--seconds`, checks every
reading, and prints the contract's line last.  It runs on a TPU that
`peaks.json` knows, or not at all.  No environment variable changes what
it does.
"""

import time

_T0 = time.perf_counter()           # process start, as near as Python gives

import argparse                     # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def fail(msg: str) -> "int":
    print(f"benchmark: {msg}", file=sys.stderr)
    return 1


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def check_device(n_chips: int, peaks: dict):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        return f"jax found platform {d.platform!r}; this benchmark runs " \
               f"on a TPU only"
    if d.device_kind not in peaks:
        return f"device kind {d.device_kind!r} is not in peaks.json"
    if len(devs) < n_chips:
        return f"the cell asks for {n_chips} chip(s), jax found {len(devs)}"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from lib import contract, paths, profile, target
    from lib.ctx import Ctx

    manifest = paths.load_manifest()
    cell = find(manifest["workloads"], args.workload, "workload")
    find(manifest["configs"], cell["config"], "configuration")
    config = target.load_config(cell["config"])
    traffic = paths.load_json("traffic", cell["traffic"] + ".json")
    reference = target.load_reference(cell["config"])
    driver = paths.load_module("drivers", traffic["driver"])

    import graphite_tpu  # noqa: F401  (x64; places the compile cache)
    import jax

    why_not = check_device(cell["chips"], paths.load_json("peaks.json"))
    if why_not:
        return fail(why_not)
    print(f"cell {cell['name']}: config {cell['config']}, traffic "
          f"{cell['traffic']}, seed {args.seed} (recorded), "
          f"{args.seconds:g} s, trace {args.trace}")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs)
        if name == COMPILE_EVENT else None)

    ctx = Ctx(cell=cell, config=config, traffic=traffic,
              reference=reference, seed=args.seed, seconds=args.seconds)
    driver.setup(ctx)
    setup_s = time.perf_counter() - _T0
    n_setup_compiles = len(compiles)
    print(f"set-up {setup_s:.3f} s ({n_setup_compiles} programs compiled "
          f"or loaded, {sum(compiles):.3f} s); spans: " + ", ".join(
              f"{n} {e - s:.3f}" for n, s, e in ctx.spans.spans))

    driver.window(ctx)
    n_window_compiles = len(compiles) - n_setup_compiles
    print(f"check compilations inside the window: {n_window_compiles} "
          f"(limit 0)")
    if n_window_compiles:
        return fail("a program compiled inside the measured window")
    device = contract.device_info(cell["chips"])

    correct, failed = driver.judge(ctx)
    values = dict(driver.end_to_end(ctx))
    values["setup_s"] = setup_s
    values["peak_hbm_gb"] = device["memory_peak_bytes"] / 1e9

    breakdown = None
    if args.trace:
        driver.traced_slice(ctx, profile.tracing)
        ctx.profile = profile.reduce_last()
        device["busy_s"] = ctx.profile["busy_s"]
        device["window_s"] = ctx.profile["window_s"]
        breakdown = ctx.profile["breakdown"]
        wanted = [m for m in manifest["per_layer"]
                  if applies(m, cell["name"])]
        metrics = {}
        for m in wanted:
            v = paths.load_module("layer_metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = v
    else:
        wanted = [m for m in manifest["end_to_end"]
                  if applies(m, cell["name"])]
        missing = [m["name"] for m in wanted if m["name"] not in values]
        if missing:
            return fail(f"the driver reported no {missing}")
        metrics = {m["name"]: values[m["name"]] for m in wanted}
    units = {m["name"]: m["unit"] for m in wanted}
    for k, v in metrics.items():
        print(f"metric {k} = {v!r} {units[k]}")
    print(contract.last_line(correct, ctx.attempted, failed, metrics,
                             units, device, breakdown))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
