"""The registry of device scope names: which layer an XLA operation is for.

`scope(name)` is `jax.named_scope(name)` for a name in `SCOPES`, and an
error for any other.  A named scope is metadata: it lands in the
`op_name` path of every operation traced inside it (`jit(run_<tag>)/
gt.quantum/while/body/gt.core/gt.net.mailbox/cond/...`) and changes no
equation, so
`PROGRAMS.lock`, `BUDGETS.json` and the compiled operation count do not
move (tests/test_scopes.py holds that).

Names are flat (no `/`) and prefixed `gt.`, so a reducer finds one as a
token of ONE segment of the path even where a transform wraps it
(`vmap(gt.net.mailbox)`).  Scopes nest; `deepest(op_name)` is the rule
every reader uses: the registered name in the path's last segment that
has one.  `gt.quantum` encloses the whole loop nest and `gt.core` a whole
`subquantum_iteration`, so an operation with any `op_name` at all belongs
to some scope; what is left unscoped is what XLA added on its own
(copies on the loop carry, parameter moves).

Applied in engine/step.py, memory/engine.py, memory/engine_shl2.py,
models/iocoom.py, models/network_hop_by_hop.py, models/network_atac.py
and parallel/px.py, at the
granularity of a layer a `perf_opt` PR would work on — not of a helper
function.
"""

import hashlib
import re

import jax

_MEM_PHASES = ("requester", "home_evict", "home_start", "sharer",
               "home_finish", "requester_fill")   # memory.engine.PHASE_NAMES

SCOPES = (
    "gt.quantum",           # outer loops: boundaries, progress, deadlock
    "gt.fetch",             # trace read + record decode
    "gt.core",              # classify, cost, commit, clock update
    "gt.core.iocoom",       # models/iocoom.iocoom_commit
    "gt.mem.base",          # memory engine outside its phases, its gate
) + tuple("gt.mem." + p for p in _MEM_PHASES) + (
    "gt.mem.stage_flush",   # dir_stage_flush, once per inner block
    "gt.mem.entry_land",    # inside gt.mem.base: the home phases' plan
                            #   landed on the u32 entry words
    "gt.mem.stage_overlay",  # staged programs: the staging table's index
                            #   at the working set's gather, and a home
                            #   phase's fetch of the staged value it reads
    "gt.mem.dir_apply",     # shl2: a home phase's row plan landed on the
                            #   embedded directory, outside its gate
    "gt.net.mailbox",       # SEND / NET_RECV rings
    "gt.net.route",         # NoC latency models, user + memory network
    "gt.net.hbh.scan",      # emesh_hop_by_hop, inside gt.net.route: path
                            #   masks, max-plus scan, per-cell delays
    "gt.net.hbh.commit",    # ... and the port occupancies' commit
    "gt.net.atac.hub",      # atac, inside gt.net.route: a unicast's two
                            #   hub-queue charges (send hub, receive hub)
    "gt.net.atac.fanout",   # ... and the ATAC leg of mem_net_fanout: the
                            #   [T, T] zero-load, ONet-pair and rank
                            #   matrices, the one send-hub charge
    "gt.sync.barrier",
    "gt.sync.mutex_cond",   # mutex + cond block, published cond signals
    "gt.sync.join",
    "gt.obs",               # telemetry / profile / hist ticks
    "gt.dvfs",
    "gt.energy",            # inside gt.dvfs's taken arm: a tile's energy
                            #   interval closed at the old operating point
    "gt.px",                # the packed shard_map exchange
)

_TOKEN = re.compile(r"gt\.[a-z0-9_.]*[a-z0-9_]")
_KNOWN = frozenset(SCOPES)

# Scopes live in the executable, and JAX's persistent-cache key ignores
# them (locations are stripped): an executable cached before a name was
# registered would be served for the scoped program and name nothing.  The
# drive loop's jitted functions therefore carry this tag in their name
# (`jit_run_s<tag>`): the module's name IS part of the key, so the key
# moves with the registry and with nothing else.
CACHE_TAG = "s" + hashlib.sha1(",".join(SCOPES).encode()).hexdigest()[:6]


def tagged(fn):
    """`fn`, renamed `<name>_<CACHE_TAG>` for `jax.jit` (see above)."""
    fn.__name__ = f"{fn.__name__}_{CACHE_TAG}"
    return fn


def scope(name: str):
    """`jax.named_scope(name)`; usable as `with scope(...):` or as a
    decorator.  Refuses a name the registry does not hold."""
    if name not in _KNOWN:
        raise ValueError(f"{name!r} is not a registered scope "
                         f"(graphite_tpu/obs/scopes.py: SCOPES)")
    return jax.named_scope(name)


def deepest(op_name: str) -> "str | None":
    """The innermost registered scope of an `op_name` path, or None."""
    for segment in reversed(op_name.split("/")):
        for token in reversed(_TOKEN.findall(segment)):
            if token in _KNOWN:
                return token
    return None
