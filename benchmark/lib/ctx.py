"""What one run knows: handed to the driver and to every metric reader."""

import dataclasses

from .clock import Spans


@dataclasses.dataclass
class Ctx:
    cell: dict                 # the `workloads` entry of BENCHMARK.json
    config: dict               # benchmark/configs/<config>.json
    traffic: dict              # benchmark/traffic/<traffic>.json
    reference: dict            # benchmark/references/<config>.json
    seed: int
    seconds: float
    spans: Spans = dataclasses.field(default_factory=Spans)
    # filled by the driver
    readings: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    raised: int = 0
    window_s: float = 0.0
    # filled by a traced run: lib.xplane.reduce()'s result
    profile: "dict | None" = None
    # the driver's own objects (the target, the compiled program, ...)
    own: dict = dataclasses.field(default_factory=dict)
