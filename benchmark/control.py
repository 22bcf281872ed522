"""The control: a run that must come out as NOT correct.

    python benchmark/control.py --workload <cell> --seed <n> --seconds <s>

The same command as run.py, over the same driver and the same checks, but
with the one guarantee broken that the configuration's file names under
`control` (a cheaper core model, a cheaper NoC model): a step that would
tempt a later PR.  Every simulated statistic is an integer and the
comparison is exact, so any such step must move the digest.  Exits 0 only
if `correct` came out false.  Not part of a benchmark run; it costs its
own compile, since a changed target is a new program.
"""

import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
from lib import target  # noqa: E402

_load_config = target.load_config


def broken(name: str) -> dict:
    config = _load_config(name)
    ctl = config["control"]
    out = dict(config)
    out["config_text"] = {**config["config_text"],
                          **ctl.get("config_text", {})}
    # what the control changes on purpose is not held against `expect`
    out["expect"] = {}
    print(f"control: breaking {ctl['breaks']}")
    return out


class _Tee(io.StringIO):
    """Keeps what passes through on its way to `out`."""

    def __init__(self, out):
        super().__init__()
        self.out = out

    def write(self, s):
        self.out.write(s)
        return super().write(s)


def main(argv=None) -> int:
    """run.py's own main over the broken configuration; its result is
    the last line it prints."""
    out = _Tee(sys.stdout)
    target.load_config = broken
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(list(argv if argv is not None else sys.argv[1:])
                          + ["--trace", "0"])
    finally:
        target.load_config = _load_config
    lines = out.getvalue().strip().splitlines()
    if rc or not lines or not lines[-1].startswith('{"correct"'):
        print("control: the run gave no result")
        return 1
    doc = json.loads(lines[-1])
    print("control result: " + json.dumps(
        {k: doc[k] for k in ("correct", "attempted", "failed")}))
    return 0 if doc["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
