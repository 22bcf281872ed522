"""Vectorized IOCOOM core model (in-order commit, out-of-order memory).

Reference: `common/tile/core/models/iocoom_core_model.{h,cc}` — a register
scoreboard over 512 registers, a load queue with optional speculative loads,
and a store queue with optional multiple outstanding RFOs and load-bypass
(`carbon_sim.cfg:180-185`).  The timing algebra per instruction
(`iocoom_core_model.cc:79-276`) is pure max/add over small fixed vectors, so
it vectorizes over the tile axis directly; the queues become [T, N] ring
scoreboards updated with one-hot dense writes (no scatters).

Semantics reproduced exactly:
 - instruction fetch: instruction_ready = curr_time + max(icache_lat - 1cy, 0)
   (`iocoom_core_model.cc:96-101`);
 - read-register operands wait on the scoreboard, split by producing unit
   (LOAD_UNIT vs EXECUTION_UNIT) for the stall breakdown (`:115-146`);
 - loads issue after all register reads; store-queue bypass returns in one
   cycle; otherwise the load queue allocates at max(head, sched) with
   speculative issue=allocate or FIFO issue=last (`:330-355`);
 - execution completes at read_operands_ready + cost; write registers are
   stamped with that time, tagged LOAD_UNIT only for simple MOV loads
   (`:185-198`);
 - stores allocate in the store queue after execution, ordered against the
   last load deallocate (TSO; `:406-436`);
 - the clock advances only to load_queue_ready (simple MOV load),
   read_operands_ready, or store_queue_ready — later work overlaps with
   younger instructions (`:240-267`);
 - seven detailed stall counters (`outputSummary`, `:64-77`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import struct

from graphite_tpu.obs.scopes import scope
from graphite_tpu.time_types import cycles_to_ps
from graphite_tpu.trace.schema import (
    FLAG_MEM0_VALID, FLAG_MEM0_WRITE, FLAG_MEM1_VALID, FLAG_MEM1_WRITE,
    FLAG_SIMPLE_MOV_LOAD, NO_REG,
)

I64 = jnp.int64

NUM_REGISTERS = 512  # `iocoom_core_model.h:77` _NUM_REGISTERS

# register_dependency_list units (`iocoom_core_model.h:13-19`)
UNIT_INVALID = 0
UNIT_LOAD = 1
UNIT_EXEC = 3


@dataclasses.dataclass(frozen=True)
class IocoomParams:
    """[core/iocoom] knobs (`carbon_sim.cfg:180-185`)."""

    num_load_queue_entries: int = 8
    num_store_queue_entries: int = 8
    speculative_loads_enabled: bool = True
    multiple_outstanding_rfos_enabled: bool = True

    @classmethod
    def from_config(cls, cfg) -> "IocoomParams":
        return cls(
            num_load_queue_entries=cfg.get_int(
                "core/iocoom/num_load_queue_entries", 8),
            num_store_queue_entries=cfg.get_int(
                "core/iocoom/num_store_queue_entries", 8),
            speculative_loads_enabled=cfg.get_bool(
                "core/iocoom/speculative_loads_enabled", True),
            multiple_outstanding_rfos_enabled=cfg.get_bool(
                "core/iocoom/multiple_outstanding_RFOs_enabled", True),
        )


@struct.dataclass
class IocoomState:
    reg_ready_ps: jax.Array   # int64[T, R] register scoreboard
    reg_unit: jax.Array       # uint8[T, R] producing unit per register
    lq_dealloc_ps: jax.Array  # int64[T, LQ] load-queue ring scoreboard
    lq_idx: jax.Array         # int32[T] next allocate index
    sq_dealloc_ps: jax.Array  # int64[T, SQ]
    sq_addr: jax.Array        # int32[T, SQ] line-granular store addresses
    sq_idx: jax.Array         # int32[T]
    # detailed pipeline stall counters (`iocoom_core_model.cc:51-61`)
    load_queue_stall_ps: jax.Array        # int64[T]
    store_queue_stall_ps: jax.Array       # int64[T]
    l1icache_stall_ps: jax.Array          # int64[T]
    intra_ins_l1dcache_stall_ps: jax.Array  # int64[T]
    inter_ins_l1dcache_stall_ps: jax.Array  # int64[T]
    intra_ins_execution_unit_stall_ps: jax.Array  # int64[T]
    inter_ins_execution_unit_stall_ps: jax.Array  # int64[T]


def init_iocoom_state(n_tiles: int, p: IocoomParams) -> IocoomState:
    T = n_tiles
    z = lambda: jnp.zeros(T, I64)  # noqa: E731
    return IocoomState(
        reg_ready_ps=jnp.zeros((T, NUM_REGISTERS), I64),
        reg_unit=jnp.zeros((T, NUM_REGISTERS), jnp.uint8),
        lq_dealloc_ps=jnp.zeros((T, p.num_load_queue_entries), I64),
        lq_idx=jnp.zeros(T, jnp.int32),
        sq_dealloc_ps=jnp.zeros((T, p.num_store_queue_entries), I64),
        sq_addr=jnp.full((T, p.num_store_queue_entries), -1, jnp.int32),
        sq_idx=jnp.zeros(T, jnp.int32),
        load_queue_stall_ps=z(), store_queue_stall_ps=z(),
        l1icache_stall_ps=z(),
        intra_ins_l1dcache_stall_ps=z(), inter_ins_l1dcache_stall_ps=z(),
        intra_ins_execution_unit_stall_ps=z(),
        inter_ins_execution_unit_stall_ps=z(),
    )


def _ring_row(arr, idx):
    """arr[t, idx[t]] via one-hot (N is small; avoids a TPU scatter)."""
    N = arr.shape[1]
    m = idx[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]
    return jnp.where(m, arr, 0).sum(axis=1)


def _ring_set(arr, idx, val, mask):
    N = arr.shape[1]
    m = (idx[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]) & (
        mask[:, None])
    return jnp.where(m, val[:, None], arr)


@scope("gt.core.iocoom")
def iocoom_commit(
    p: IocoomParams,
    ioc: IocoomState,
    *,
    commit,            # bool[T] — instruction-like lanes committing now
    clock_ps,          # int64[T] current core clock
    freq_mhz,          # int64[T]
    cost_ps,           # int64[T] execution cost of the record
    flags,             # int32[T]
    rreg0, rreg1, wreg,  # uint16-ish int[T]
    addr0, addr1,      # uint32[T]
    slot_lat_ps,       # int64[T, 3] [icache, mem0, mem1]
    enabled,           # bool[] models enabled
):
    """One committing record per lane through the IOCOOM pipeline algebra.

    Returns (new_state, new_clock_ps, memory_stall_ps, execution_stall_ps)
    for the committing lanes (others pass through unchanged).
    """
    T = clock_ps.shape[0]
    tiles = jnp.arange(T, dtype=jnp.int32)
    one_cycle = cycles_to_ps(jnp.ones(T, I64), freq_mhz)
    commit = commit & enabled  # models disabled → whole model is a no-op

    # --- instruction fetch ------------------------------------------------
    icache_lat = slot_lat_ps[:, 0]
    icache_lat = jnp.where(icache_lat >= one_cycle,
                           icache_lat - one_cycle, icache_lat)
    instruction_ready = clock_ps + icache_lat

    # --- read-register operands ------------------------------------------
    def reg_read(r):
        valid = r != NO_REG
        rr = jnp.clip(r, 0, NUM_REGISTERS - 1).astype(jnp.int32)
        ready = jnp.take_along_axis(ioc.reg_ready_ps, rr[:, None], axis=1)[:, 0]
        unit = jnp.take_along_axis(ioc.reg_unit, rr[:, None], axis=1)[:, 0]
        lt = jnp.where(valid & (unit == UNIT_LOAD), ready, 0)
        et = jnp.where(valid & (unit == UNIT_EXEC), ready, 0)
        return lt, et

    l0, e0 = reg_read(rreg0)
    l1, e1 = reg_read(rreg1)
    ready_load_unit = jnp.maximum(instruction_ready, jnp.maximum(l0, l1))
    ready_exec_unit = jnp.maximum(instruction_ready, jnp.maximum(e0, e1))
    register_operands_ready = jnp.maximum(ready_load_unit, ready_exec_unit)

    # --- memory operand decomposition ------------------------------------
    m0_valid = (flags & FLAG_MEM0_VALID) != 0
    m1_valid = (flags & FLAG_MEM1_VALID) != 0
    m0_write = (flags & FLAG_MEM0_WRITE) != 0
    m1_write = (flags & FLAG_MEM1_WRITE) != 0
    simple_mov_load = (flags & FLAG_SIMPLE_MOV_LOAD) != 0
    line0 = (addr0 >> 6).astype(jnp.int32)
    line1 = (addr1 >> 6).astype(jnp.int32)

    # --- loads (`executeLoad` + LoadQueue::execute) -----------------------
    lq = ioc.lq_dealloc_ps
    lq_idx = ioc.lq_idx
    LQ = lq.shape[1]
    load_queue_ready = register_operands_ready
    read_mem_ready = register_operands_ready

    def do_load(lq, lq_idx, lqr, rmr, line, lat, is_load):
        sched = register_operands_ready
        # store-queue bypass (`isAddressAvailable`): any SQ entry with the
        # address whose deallocate >= sched
        byp = jnp.any(
            (ioc.sq_addr == line[:, None])
            & (ioc.sq_dealloc_ps >= sched[:, None]), axis=1)
        use_lq = is_load & ~byp
        load_lat = lat + one_cycle  # store-queue check cycle
        head = _ring_row(lq, lq_idx % LQ)
        last = _ring_row(lq, (lq_idx + LQ - 1) % LQ)
        alloc = jnp.maximum(head, sched)
        if p.speculative_loads_enabled:
            completion = alloc + load_lat
            dealloc = jnp.maximum(completion, last + one_cycle)
        else:
            issue = jnp.maximum(last, sched)
            completion = issue + load_lat
            dealloc = completion
        lq = _ring_set(lq, lq_idx % LQ, dealloc, use_lq)
        lq_idx = lq_idx + use_lq.astype(jnp.int32)
        alloc = jnp.where(byp, sched, alloc)
        completion = jnp.where(byp, sched + one_cycle, completion)
        lqr = jnp.where(is_load, jnp.maximum(lqr, alloc), lqr)
        rmr = jnp.where(is_load, jnp.maximum(rmr, completion), rmr)
        return lq, lq_idx, lqr, rmr

    is_load0 = commit & m0_valid & ~m0_write
    is_load1 = commit & m1_valid & ~m1_write
    lq, lq_idx, load_queue_ready, read_mem_ready = do_load(
        lq, lq_idx, load_queue_ready, read_mem_ready,
        line0, slot_lat_ps[:, 1], is_load0)
    lq, lq_idx, load_queue_ready, read_mem_ready = do_load(
        lq, lq_idx, load_queue_ready, read_mem_ready,
        line1, slot_lat_ps[:, 2], is_load1)

    # --- execution --------------------------------------------------------
    read_operands_ready = read_mem_ready
    write_operands_ready = read_operands_ready + cost_ps

    # --- write-register operands -----------------------------------------
    w_valid = commit & (wreg != NO_REG)
    wr = jnp.clip(wreg, 0, NUM_REGISTERS - 1).astype(jnp.int32)
    w_unit = jnp.where(simple_mov_load, UNIT_LOAD, UNIT_EXEC).astype(jnp.uint8)
    # (tiles, wr) pairs are unique per lane → delta-add scatters alias
    old_ready = jnp.take_along_axis(ioc.reg_ready_ps, wr[:, None], axis=1)[:, 0]
    old_unit = jnp.take_along_axis(ioc.reg_unit, wr[:, None], axis=1)[:, 0]
    reg_ready = ioc.reg_ready_ps.at[tiles, wr].add(
        jnp.where(w_valid, write_operands_ready - old_ready, 0))
    reg_unit = ioc.reg_unit.at[tiles, wr].add(
        jnp.where(w_valid, w_unit - old_unit, 0).astype(jnp.uint8))

    # --- stores (`executeStore` + StoreQueue::execute) --------------------
    sq = ioc.sq_dealloc_ps
    sq_addr = ioc.sq_addr
    sq_idx = ioc.sq_idx
    SQ = sq.shape[1]
    last_load_dealloc = _ring_row(lq, (lq_idx + LQ - 1) % LQ)
    store_queue_ready = write_operands_ready

    def do_store(sq, sq_addr, sq_idx, sqr, line, lat, is_store):
        sched = write_operands_ready
        store_lat = lat + one_cycle  # load-queue check cycle
        head = _ring_row(sq, sq_idx % SQ)
        last = _ring_row(sq, (sq_idx + SQ - 1) % SQ)
        alloc = jnp.maximum(head, sched)
        if p.multiple_outstanding_rfos_enabled:
            completion = alloc + store_lat
            dealloc = jnp.maximum(
                jnp.maximum(completion, last + one_cycle), last_load_dealloc)
        else:
            issue = jnp.maximum(jnp.maximum(sched, last), last_load_dealloc)
            completion = issue + store_lat
            dealloc = completion
        sq = _ring_set(sq, sq_idx % SQ, dealloc, is_store)
        sq_addr = _ring_set(
            sq_addr, sq_idx % SQ, line, is_store).astype(jnp.int32)
        sq_idx = sq_idx + is_store.astype(jnp.int32)
        sqr = jnp.where(is_store, jnp.maximum(sqr, alloc), sqr)
        return sq, sq_addr, sq_idx, sqr

    is_store0 = commit & m0_valid & m0_write
    is_store1 = commit & m1_valid & m1_write
    sq, sq_addr, sq_idx, store_queue_ready = do_store(
        sq, sq_addr, sq_idx, store_queue_ready,
        line0, slot_lat_ps[:, 1], is_store0)
    sq, sq_addr, sq_idx, store_queue_ready = do_store(
        sq, sq_addr, sq_idx, store_queue_ready,
        line1, slot_lat_ps[:, 2], is_store1)

    # --- clock advance + stall breakdown (`iocoom_core_model.cc:222-267`) -
    has_write_mem = m0_write & m0_valid | (m1_write & m1_valid)
    new_clock = load_queue_ready
    new_clock = jnp.where(~simple_mov_load, read_operands_ready, new_clock)
    new_clock = jnp.where(~simple_mov_load & has_write_mem,
                          store_queue_ready, new_clock)

    l1i_stall = instruction_ready - clock_ps
    inter_exec = ready_exec_unit - instruction_ready
    inter_l1d = register_operands_ready - ready_exec_unit
    lq_stall = load_queue_ready - register_operands_ready
    intra_l1d = jnp.where(~simple_mov_load,
                          read_mem_ready - load_queue_ready, 0)
    intra_exec = jnp.where(
        ~simple_mov_load & has_write_mem,
        write_operands_ready - read_operands_ready, 0)
    sq_stall = jnp.where(
        ~simple_mov_load & has_write_mem,
        store_queue_ready - write_operands_ready, 0)

    memory_stall = l1i_stall + inter_l1d + lq_stall + intra_l1d + sq_stall
    execution_stall = inter_exec + intra_exec

    def acc(counter, delta):
        return counter + jnp.where(commit, delta, 0)

    new_ioc = ioc.replace(
        reg_ready_ps=reg_ready,
        reg_unit=reg_unit,
        lq_dealloc_ps=lq,
        lq_idx=lq_idx,
        sq_dealloc_ps=sq,
        sq_addr=sq_addr,
        sq_idx=sq_idx,
        load_queue_stall_ps=acc(ioc.load_queue_stall_ps, lq_stall),
        store_queue_stall_ps=acc(ioc.store_queue_stall_ps, sq_stall),
        l1icache_stall_ps=acc(ioc.l1icache_stall_ps, l1i_stall),
        intra_ins_l1dcache_stall_ps=acc(
            ioc.intra_ins_l1dcache_stall_ps, intra_l1d),
        inter_ins_l1dcache_stall_ps=acc(
            ioc.inter_ins_l1dcache_stall_ps, inter_l1d),
        intra_ins_execution_unit_stall_ps=acc(
            ioc.intra_ins_execution_unit_stall_ps, intra_exec),
        inter_ins_execution_unit_stall_ps=acc(
            ioc.inter_ins_execution_unit_stall_ps, inter_exec),
    )
    new_clock = jnp.where(commit, new_clock, clock_ps)
    memory_stall = jnp.where(commit, memory_stall, 0)
    execution_stall = jnp.where(commit, execution_stall, 0)
    return new_ioc, new_clock, memory_stall, execution_stall
