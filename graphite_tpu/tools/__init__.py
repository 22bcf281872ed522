"""Host-side tools: output parsing and the drivers — the analog of
the reference's `tools/` directory (`tools/parse_output.py`; its
`tools/regress/run_tests.py` is this repo's `tests/`).  Multi-machine
spawn helpers (`tools/spawn*.py`, `schedule.py`) have no TPU analog:
distribution is `shard_map` over the device mesh, not process spawning
(SURVEY §2.10)."""
