"""A configuration file -> the target the program simulates.

Everything that belongs to one configuration is data in
`benchmark/configs/<name>.json`; this is the one general builder.
"""

import importlib
import re

from . import paths

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# what `correct` holds every configuration to; a configuration's file
# states all of them and may not leave one out
GUARANTEES = (
    "func_errors_zero",
    "every_tile_clock_advances",
    "no_mailbox_overflow_or_deadlock",
    "total_instructions_equal_trace_count",
    "readings_bit_identical",
    "statistics_equal_reference_exactly",
)
_TRACE_MODULES = ("benchmarks", "synthetic")


def load_config(name: str) -> dict:
    if not NAME.match(name):
        raise ValueError(f"bad configuration name {name!r}")
    cfg = paths.load_json("configs", name + ".json")
    for key in ("source", "why", "assumed", "reduced", "config_text",
                "trace", "simulator", "expect", "guarantees"):
        if key not in cfg:
            raise ValueError(f"configs/{name}.json lacks {key!r}")
    if len(cfg["source"]) > 200:
        raise ValueError(f"configs/{name}.json: source over 200 characters")
    if sorted(cfg["guarantees"]) != sorted(GUARANTEES):
        raise ValueError(
            f"configs/{name}.json must state every guarantee: {GUARANTEES}")
    if cfg["trace"]["module"] not in _TRACE_MODULES:
        raise ValueError(f"configs/{name}.json: trace module must be one "
                         f"of {_TRACE_MODULES}")
    return cfg


def load_reference(name: str) -> dict:
    return paths.load_json("references", name + ".json")


def build_trace(cfg: dict):
    mod = importlib.import_module(
        "graphite_tpu.trace." + cfg["trace"]["module"])
    return getattr(mod, cfg["trace"]["function"])(**cfg["trace"]["kwargs"])


def build_sim_config(cfg: dict):
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.tools._template import config_text

    args = dict(cfg["config_text"])
    return SimConfig(ConfigFile.from_string(
        config_text(args.pop("tiles"), **args)))


def _lookup(obj, dotted: str):
    for part in dotted.split("."):
        obj = getattr(obj, part)
    return obj


def check_expectations(cfg: dict, sim) -> None:
    """The configuration's `expect`: attributes of the built `Simulator`
    (dotted paths) that must hold, so that a default that drifts in the
    program cannot silently change what the cell simulates.  A value
    {"is_none": false} asks only that the attribute is set."""
    bad = []
    for path, want in cfg["expect"].items():
        got = _lookup(sim, path)
        if isinstance(want, dict) and "is_none" in want:
            ok = (got is None) == want["is_none"]
        else:
            ok = got == want
        if not ok:
            bad.append(f"{path}={got!r} (want {want!r})")
    if bad:
        raise SystemExit("benchmark: the built target does not carry the "
                         "configuration: " + "; ".join(bad))
