"""What decides `correct`: every reading of the window against the
configuration's guarantees and its reference, exactly.

Each number compared is printed beside its limit; every limit is 0.
"""

import numpy as np

from . import digest


def trace_instructions(batch) -> int:
    """The trace's own instruction count, independent of the engine:
    every static/branch/dynamic record is one instruction (SPAWN is not),
    a BBLOCK record carries its count in aux0."""
    from graphite_tpu.trace.schema import Op

    op = batch.op
    one = (op < 20) & (op != int(Op.SPAWN))
    return int(one.sum()) + int(batch.aux0[op == int(Op.BBLOCK)].sum())


def trace_records(batch) -> int:
    """The trace's own record count: what the engine retires, one record
    of one tile per iteration at most.  Everything but the NOP padding
    past a tile's THREAD_EXIT.  (A BBLOCK is ONE record, whatever number
    of instructions it stands for.)"""
    from graphite_tpu.trace.schema import Op

    return int((batch.op != int(Op.NOP)).sum())


def check_reading(res, n_trace_instr: int) -> "dict[str, int]":
    """The engine-independent numbers of one reading (each must be 0).
    A mailbox overflow or a deadlock raises inside run(): a reading that
    returned had neither."""
    want = (n_trace_instr + int(res.recv_instructions.sum())
            + int(res.sync_instructions.sum()))
    return {
        "func_errors": int(res.func_errors),
        "tiles_whose_clock_did_not_advance":
            int((np.asarray(res.clock_ps) <= 0).sum()),
        "total_instructions_minus_trace_count":
            int(res.total_instructions) - want,
    }


def judge(readings: list, raised: int, n_trace_instr: int,
          reference: dict, out=print) -> "tuple[bool, int]":
    """(correct, failed readings).  `readings` are the SimResults of the
    window, in order; `raised` counts readings that raised instead."""
    worst = {"func_errors": 0, "tiles_whose_clock_did_not_advance": 0,
             "total_instructions_minus_trace_count": 0}
    ref_stats = reference["statistics"]
    first = None
    n_failed = raised
    differ_ref, differ_first = set(), set()
    for res in readings:
        nums = check_reading(res, n_trace_instr)
        for k, v in nums.items():
            if abs(v) > abs(worst[k]):
                worst[k] = v
        hs = digest.hashes(digest.statistics(res))
        if first is None:
            first = hs
        bad_first = [k for k in first if hs.get(k) != first[k]]
        bad_ref = digest.compare(hs, ref_stats)
        differ_first.update(bad_first)
        differ_ref.update(bad_ref)
        if any(nums.values()) or bad_first or bad_ref:
            n_failed += 1
    out(f"check readings that raised: {raised} (limit 0)")
    for k, v in worst.items():
        out(f"check {k}, worst reading: {v} (limit 0)")
    out(f"check statistics differing between readings: "
        f"{len(differ_first)} {sorted(differ_first)[:6]} (limit 0)")
    origins = sorted({v["origin"] for v in ref_stats.values()})
    contested = sorted(k for k, v in ref_stats.items() if v.get("disagrees"))
    out(f"check statistics differing from the reference "
        f"({'+'.join(origins)}, {len(ref_stats)} statistics, "
        f"digest {reference['digest'][:16]}): {len(differ_ref)} "
        f"{sorted(differ_ref)[:6]} (limit 0)")
    if contested:
        out(f"note: another origin of the reference disagrees on "
            f"{len(contested)} statistics {contested[:3]}...: PERF.md")
    got = digest.combined({k: first[k] for k in ref_stats if k in first}) \
        if first else None
    out(f"check digest of the run: {got and got[:16]} (reference "
        f"{reference['digest'][:16]})")
    correct = bool(readings) and n_failed == 0 \
        and got == reference["digest"]
    return correct, n_failed
