"""Trace record schema: the instruction/event stream consumed by the engine.

The op space unifies two reference concepts:
 - `InstructionType` (`common/tile/core/instruction.h:20-43`): the static
   instruction classes whose costs come from
   `[core/static_instruction_costs]` (`carbon_sim.cfg:189-200`), plus the
   dynamic classes (recv/sync/spawn/stall, `instruction.h:149-198`);
 - the user-API calls that Pin's routine replacement intercepts
   (`pin/routine_replace.cc:37-101`): CAPI send/recv (`capi.h:18-24`),
   mutex/cond/barrier (`sync_api.h:19-34`), thread spawn/join
   (`thread_support.h:66-71`), DVFS get/set (`dvfs.h:42-48`), model toggles
   (`performance_counter_support.h:8-9`).

Record layout (struct-of-arrays, leading axes [n_tiles, T]):

    op        uint8   opcode (Op enum below)
    flags     uint8   bit0-1: mem-op slot valid; bit2-3: slot is-write;
                      bit4: branch taken; bit5: atomic
    pc        uint32  instruction address (icache + branch predictor index)
    addr0/1   uint32  memory operand addresses (slot 0 / slot 1)
    size0/1   uint8   memory operand sizes in bytes
    aux0      int32   partner tile / sync-object id / dvfs domain
    aux1      int32   message size / barrier count / frequency (MHz)
    dyn_ps    int64   dynamic-instruction cost in ps (Op.SPAWN: absolute time)

32 bytes per record; a 1024-tile x 1M-instruction trace is 32 GB streamed in
windows, or generated on device.  Memory operands are pre-split at cache-line
boundaries by producers (the reference splits in
`core.cc:140-267 initiateMemoryAccess`).
"""

from __future__ import annotations

import dataclasses
import enum
import functools

import numpy as np

from graphite_tpu.obs.trace import SetupSpans


MAX_MEM_OPS = 2  # matches Pin operand scan (`pin/instruction_modeling.cc:33-124`)

# flags bits
FLAG_MEM0_VALID = 1 << 0
FLAG_MEM1_VALID = 1 << 1
FLAG_MEM0_WRITE = 1 << 2
FLAG_MEM1_WRITE = 1 << 3
FLAG_BRANCH_TAKEN = 1 << 4
FLAG_ATOMIC = 1 << 5
# Self-checking-test hook (the engine's analog of the reference unit tests'
# assert-based checking, e.g. `tests/unit/shared_mem_test1`): a load with
# FLAG_CHECK compares the loaded word against aux0 and bumps a global
# functional-error counter on mismatch.
FLAG_CHECK = 1 << 6
# MOV whose only memory operand is a single load (`Instruction::
# isSimpleMovMemoryLoad`): the iocoom model lets the next instruction issue
# at load-queue allocate time instead of load completion.
FLAG_SIMPLE_MOV_LOAD = 1 << 7


class Op(enum.IntEnum):
    """Unified opcode space.

    0-19 mirror `InstructionType` (`instruction.h:20-43`) in order, so the
    static-cost table indexes directly.  32+ are user-API events.
    """

    GENERIC = 0
    MOV = 1
    IALU = 2
    IMUL = 3
    IDIV = 4
    FALU = 5
    FMUL = 6
    FDIV = 7
    XMM_SS = 8
    XMM_SD = 9
    XMM_PS = 10
    BRANCH = 11
    LFENCE = 12
    SFENCE = 13
    MFENCE = 14
    DYNAMIC_MISC = 15
    RECV = 16
    SYNC = 17
    SPAWN = 18
    STALL = 19
    # --- user-API events (L7 surface) ---
    SEND = 32          # CAPI_message_send_w:   aux0=dest tile, aux1=bytes
    NET_RECV = 33      # CAPI_message_receive_w: aux0=sender tile, aux1=bytes
    MUTEX_INIT = 34    # aux0=mutex id
    MUTEX_LOCK = 35    # aux0=mutex id
    MUTEX_UNLOCK = 36  # aux0=mutex id
    COND_INIT = 37     # aux0=cond id
    COND_WAIT = 38     # aux0=cond id, aux1=mutex id
    COND_SIGNAL = 39   # aux0=cond id
    COND_BROADCAST = 40  # aux0=cond id
    BARRIER_INIT = 41  # aux0=barrier id, aux1=count
    BARRIER_WAIT = 42  # aux0=barrier id
    THREAD_SPAWN = 43  # aux0=target tile
    THREAD_JOIN = 44   # aux0=target tile
    THREAD_EXIT = 45   # end of this tile's stream
    ENABLE_MODELS = 46
    DISABLE_MODELS = 47
    DVFS_SET = 48      # aux0=domain, aux1=frequency in MHz
    DVFS_GET = 49      # aux0=domain
    # Compressed straight-line run: aux0 = instruction count, aux1 = total
    # cycles (sum of per-instruction static costs).  The TPU-native analog
    # of Pin's basic-block granularity (`pin/instruction_modeling.cc`
    # instruments per-INS but the cost algebra over a run of static-cost
    # instructions is associative, so one record carries the whole run —
    # cycle-identical at fixed frequency when icache modeling is off, and
    # DVFS changes only occur at DVFS_SET records, never inside a run).
    # With icache modeling ON, a BBLOCK pays ONE icache fetch for its first
    # line (record pc) rather than per-line fetches — a documented
    # block-granularity approximation.  No memory operands, branches, or
    # events inside a run.
    BBLOCK = 50
    # Syscall rerouted to the central SyscallServer on the MCP tile
    # (`syscall_model.cc:132-244` marshals to MCP; `syscall_server.cc`
    # executes): aux0 = syscall class (SYS_* below), aux1 = arg (bytes).
    # Functional execution happens host-side (system/syscall_server.py);
    # replay charges the SYSTEM-network round trip to the MCP.
    SYSCALL = 51
    # --- co-located-thread sync forms (the live frontend's split ops) ---
    # Threads sharing a tile serialize onto ONE engine lane; a blocking
    # record whose resolution lies LATER on the same lane would deadlock
    # the replay.  The live frontend therefore splits blocking sync into a
    # non-blocking contribution at call time and a rendezvous at functional
    # completion time (recorded after the thread is rescheduled, hence
    # after any co-located segments that ran meanwhile):
    BARRIER_ARRIVE = 52  # aux0=barrier id: count the arrival, don't block
    BARRIER_SYNC = 53    # aux0=id, aux1=generation: wait for release #gen
    COND_JOIN = 54       # aux0=cond id, aux1=signal seq: wait for it, take
    #                      its time (pairs with MUTEX_UNLOCK at wait start
    #                      + MUTEX_LOCK re-acquire after)
    NOP = 255          # padding past THREAD_EXIT


# Syscall classes marshalled to the MCP SyscallServer (the reference
# handles ~25 in `syscall_model.cc:132-244`; ids here are internal).
SYS_OPEN = 0
SYS_CLOSE = 1
SYS_READ = 2
SYS_WRITE = 3
SYS_LSEEK = 4
SYS_ACCESS = 5
SYS_UNLINK = 6
SYS_STAT = 7
SYS_BRK = 8
SYS_MMAP = 9
SYS_MUNMAP = 10
SYS_FUTEX = 11
SYS_GETPID = 12
SYS_OTHER = 13


N_STATIC_INSTRUCTION_TYPES = 20  # MAX_INSTRUCTION_COUNT (`instruction.h:42`)

STATIC_COST_KEYS = (
    # `INSTRUCTION_NAMES` (`instruction.h:45-46`); costs read from
    # core/static_instruction_costs/<name> with default 0
    # (`core_model.cc:65-76`).
    "generic", "mov", "ialu", "imul", "idiv", "falu", "fmul", "fdiv",
    "xmm_ss", "xmm_sd", "xmm_ps", "branch", "lfence", "sfence", "mfence",
    "dynamic_misc", "recv", "sync", "spawn", "stall",
)

NO_REG = 0xFFFF  # sentinel: operand slot unused

_FIELDS = (
    ("op", np.uint8),
    ("flags", np.uint8),
    ("pc", np.uint32),
    ("addr0", np.uint32),
    ("addr1", np.uint32),
    ("size0", np.uint8),
    ("size1", np.uint8),
    ("aux0", np.int32),
    ("aux1", np.int32),
    ("dyn_ps", np.int64),
    # register operands (iocoom scoreboard; `instruction.h` RegisterOperand
    # lists, bounded to 2 reads + 1 write per record).  NO_REG = unused.
    ("rreg0", np.uint16),
    ("rreg1", np.uint16),
    ("wreg", np.uint16),
)


@dataclasses.dataclass
class TraceBatch:
    """A padded batch of per-tile traces, shape [n_tiles, length] per field."""

    op: np.ndarray
    flags: np.ndarray
    pc: np.ndarray
    addr0: np.ndarray
    addr1: np.ndarray
    size0: np.ndarray
    size1: np.ndarray
    aux0: np.ndarray
    aux1: np.ndarray
    dyn_ps: np.ndarray
    rreg0: np.ndarray
    rreg1: np.ndarray
    wreg: np.ndarray

    @property
    def n_tiles(self) -> int:
        return self.op.shape[0]

    @property
    def length(self) -> int:
        return self.op.shape[1]

    def save(self, path: str) -> None:
        from graphite_tpu.trace.io import save_trace_npz

        save_trace_npz(path, self)

    @classmethod
    def load(cls, path: str) -> "TraceBatch":
        from graphite_tpu.trace.io import load_trace_npz

        return load_trace_npz(path)

    @classmethod
    def from_builders(cls, builders: "list[TraceBuilder]") -> "TraceBatch":
        """Pad per-tile streams to a common length with THREAD_EXIT + NOP."""
        for b in builders:
            if not b._op or b._op[-1] != Op.THREAD_EXIT:
                b.exit()
        length = max(len(b._op) for b in builders)
        n = len(builders)
        arrays = {
            name: np.zeros((n, length), dtype=dtype) for name, dtype in _FIELDS
        }
        arrays["op"][:] = int(Op.NOP)
        for reg_field in ("rreg0", "rreg1", "wreg"):
            arrays[reg_field][:] = NO_REG
        for t, b in enumerate(builders):
            for name, _ in _FIELDS:
                col = getattr(b, "_" + name)
                arrays[name][t, : len(col)] = col
        return cls(**arrays)


def generator(build):
    """Decorator of a trace generator: the call is the set-up span
    `build_trace` (obs/trace.py: SETUP_SPANS), with the batch's tiles and
    records (every record but the NOP padding)."""

    @functools.wraps(build)
    def build_trace(*args, **kwargs):
        with SetupSpans()("build_trace", generator=build.__name__) as span:
            batch = build(*args, **kwargs)
            span.attrs.update(
                tiles=batch.n_tiles,
                records=int(np.count_nonzero(batch.op != int(Op.NOP))))
        return batch

    return build_trace


class TraceBuilder:
    """Append-records-for-one-tile helper used by generators and tests."""

    def __init__(self) -> None:
        for name, _ in _FIELDS:
            setattr(self, "_" + name, [])

    def _append(self, op, flags=0, pc=0, addr0=0, addr1=0, size0=0, size1=0,
                aux0=0, aux1=0, dyn_ps=0, rreg0=NO_REG, rreg1=NO_REG,
                wreg=NO_REG) -> "TraceBuilder":
        self._op.append(int(op))
        self._flags.append(flags)
        self._pc.append(pc)
        self._addr0.append(addr0)
        self._addr1.append(addr1)
        self._size0.append(size0)
        self._size1.append(size1)
        self._aux0.append(aux0)
        self._aux1.append(aux1)
        self._dyn_ps.append(dyn_ps)
        self._rreg0.append(rreg0)
        self._rreg1.append(rreg1)
        self._wreg.append(wreg)
        return self

    # --- instructions ----------------------------------------------------

    def instr(self, op: Op, pc: int = 0, rregs=(), wreg: int = NO_REG,
              ) -> "TraceBuilder":
        """A compute instruction with no memory operands."""
        rr = tuple(rregs) + (NO_REG, NO_REG)
        return self._append(op, pc=pc, rreg0=rr[0], rreg1=rr[1], wreg=wreg)

    def load(self, addr: int, size: int = 4, pc: int = 0,
             op: Op = Op.MOV, rregs=(), wreg: int = NO_REG,
             ) -> "TraceBuilder":
        flags = FLAG_MEM0_VALID
        if op == Op.MOV:
            flags |= FLAG_SIMPLE_MOV_LOAD
        rr = tuple(rregs) + (NO_REG, NO_REG)
        return self._append(op, flags=flags, pc=pc,
                            addr0=addr, size0=size,
                            rreg0=rr[0], rreg1=rr[1], wreg=wreg)

    def store(self, addr: int, size: int = 4, pc: int = 0,
              op: Op = Op.MOV, rregs=(), wreg: int = NO_REG,
              ) -> "TraceBuilder":
        rr = tuple(rregs) + (NO_REG, NO_REG)
        return self._append(op, flags=FLAG_MEM0_VALID | FLAG_MEM0_WRITE,
                            pc=pc, addr0=addr, size0=size,
                            rreg0=rr[0], rreg1=rr[1], wreg=wreg)

    def store_value(self, addr: int, value: int, size: int = 4, pc: int = 0,
                    op: Op = Op.MOV) -> "TraceBuilder":
        """Store with a functional value (engine writes `value` to the word)."""
        return self._append(op, flags=FLAG_MEM0_VALID | FLAG_MEM0_WRITE,
                            pc=pc, addr0=addr, size0=size, aux0=value)

    def load_check(self, addr: int, expect: int, size: int = 4,
                   pc: int = 0, op: Op = Op.MOV) -> "TraceBuilder":
        """Self-checking load: bumps the functional-error counter unless the
        loaded word equals `expect` (FLAG_CHECK)."""
        flags = FLAG_MEM0_VALID | FLAG_CHECK
        if op == Op.MOV:
            flags |= FLAG_SIMPLE_MOV_LOAD
        return self._append(op, flags=flags, pc=pc,
                            addr0=addr, size0=size, aux0=expect)

    def load_store(self, raddr: int, waddr: int, size: int = 4,
                   pc: int = 0, op: Op = Op.GENERIC) -> "TraceBuilder":
        flags = (FLAG_MEM0_VALID | FLAG_MEM1_VALID | FLAG_MEM1_WRITE)
        return self._append(op, flags=flags, pc=pc, addr0=raddr,
                            addr1=waddr, size0=size, size1=size)

    def bblock(self, n_instr: int, cycles: int, pc: int = 0) -> "TraceBuilder":
        """A compressed run of `n_instr` straight-line instructions costing
        `cycles` total (Op.BBLOCK)."""
        return self._append(Op.BBLOCK, pc=pc, aux0=n_instr, aux1=cycles)

    def branch(self, taken: bool, pc: int = 0) -> "TraceBuilder":
        flags = FLAG_BRANCH_TAKEN if taken else 0
        return self._append(Op.BRANCH, flags=flags, pc=pc)

    def dynamic(self, op: Op, cost_ps: int) -> "TraceBuilder":
        return self._append(op, dyn_ps=cost_ps)

    # --- user-API events -------------------------------------------------

    def send(self, dest: int, size: int = 8) -> "TraceBuilder":
        return self._append(Op.SEND, aux0=dest, aux1=size)

    def recv(self, sender: int, size: int = 8) -> "TraceBuilder":
        return self._append(Op.NET_RECV, aux0=sender, aux1=size)

    def mutex_init(self, mux: int) -> "TraceBuilder":
        return self._append(Op.MUTEX_INIT, aux0=mux)

    def mutex_lock(self, mux: int) -> "TraceBuilder":
        return self._append(Op.MUTEX_LOCK, aux0=mux)

    def mutex_unlock(self, mux: int) -> "TraceBuilder":
        return self._append(Op.MUTEX_UNLOCK, aux0=mux)

    def cond_init(self, cond: int) -> "TraceBuilder":
        return self._append(Op.COND_INIT, aux0=cond)

    def cond_wait(self, cond: int, mux: int) -> "TraceBuilder":
        return self._append(Op.COND_WAIT, aux0=cond, aux1=mux)

    def cond_signal(self, cond: int, publish: bool = False) -> "TraceBuilder":
        # publish=True: the live frontend's sequence-published form (bumps
        # the cond's signal counter for COND_JOIN waiters)
        return self._append(Op.COND_SIGNAL, aux0=cond,
                            aux1=1 if publish else 0)

    def cond_broadcast(self, cond: int,
                       publish: bool = False) -> "TraceBuilder":
        return self._append(Op.COND_BROADCAST, aux0=cond,
                            aux1=1 if publish else 0)

    def cond_join(self, cond: int, seq: int) -> "TraceBuilder":
        return self._append(Op.COND_JOIN, aux0=cond, aux1=seq)

    def barrier_arrive(self, bar: int) -> "TraceBuilder":
        return self._append(Op.BARRIER_ARRIVE, aux0=bar)

    def barrier_sync(self, bar: int, generation: int) -> "TraceBuilder":
        return self._append(Op.BARRIER_SYNC, aux0=bar, aux1=generation)

    def barrier_init(self, bar: int, count: int) -> "TraceBuilder":
        return self._append(Op.BARRIER_INIT, aux0=bar, aux1=count)

    def barrier_wait(self, bar: int) -> "TraceBuilder":
        return self._append(Op.BARRIER_WAIT, aux0=bar)

    def thread_spawn(self, target_tile: int) -> "TraceBuilder":
        return self._append(Op.THREAD_SPAWN, aux0=target_tile)

    def thread_join(self, target_tile: int) -> "TraceBuilder":
        return self._append(Op.THREAD_JOIN, aux0=target_tile)

    def exit(self) -> "TraceBuilder":
        return self._append(Op.THREAD_EXIT)

    def syscall(self, sc_class: int, arg: int = 0) -> "TraceBuilder":
        return self._append(Op.SYSCALL, aux0=sc_class, aux1=arg)

    def dvfs_set(self, domain: int, freq_mhz: int,
                 hold: bool = False) -> "TraceBuilder":
        """Retune a DVFS domain; hold=True keeps the current voltage
        (fails if the frequency exceeds its maximum — `dvfs.h` HOLD)."""
        return self._append(Op.DVFS_SET, aux0=domain,
                            aux1=-freq_mhz if hold else freq_mhz)
