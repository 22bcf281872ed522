"""Ask the chip's compiler, without the chip: the fused quantum-loop
programs of the main path compiled for a DESCRIBED v5e:2x2 topology.

The suite runs on the CPU, and the CPU backend accepts programs the TPU
compiler may refuse (int64 emulation, scatter staging, HBM footprint).
These tests lower the programs `Simulator.run()` dispatches from
`ShapeDtypeStruct`s placed on a described device and compile them with
the installed TPU compiler.  A compile that passes is NOT a chip run:
nothing executes, and no time or result comes out of it.

The topology is described inside the module-scoped fixture below and
nowhere else (only one process may load the TPU library, and xdist
workers import every test file); shardings, meshes and shapes are built
in fixtures or tests.  The persistent compile cache is off around these
compiles: a described-topology entry can be written but never read back
without a chip.
"""

import numpy as np
import pytest

import jax
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.tools._template import (
    coherence_stress_workload, config_text,
)
from graphite_tpu.trace.benchmarks import fft_trace, radix_trace

HBM_BYTES = 16 * 10**9     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    from graphite_tpu.store.aot import _fresh_codegen

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # jax_enable_compilation_cache off + reset_cache(), restored after
    with _fresh_codegen():
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """The pytree as ShapeDtypeStructs carrying `sharding` (a sharding,
    or a same-structure pytree of them)."""
    shardings = (sharding if not isinstance(sharding, jax.sharding.Sharding)
                 else jax.tree.map(lambda _: sharding, tree))
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=s),
        tree, shardings)


def _report(name, compiled):
    m = compiled.memory_analysis()
    row = {"program": name,
           "code_bytes": m.generated_code_size_in_bytes,
           "argument_bytes": m.argument_size_in_bytes,
           "output_bytes": m.output_size_in_bytes,
           "alias_bytes": m.alias_size_in_bytes,
           "temp_bytes": m.temp_size_in_bytes}
    print(row)
    return row


def _fits(row):
    live = (row["argument_bytes"] + row["output_bytes"]
            - row["alias_bytes"] + row["temp_bytes"] + row["code_bytes"])
    assert live < HBM_BYTES, row


def _ref_default(tiles, points):
    """The reference-default coherent target (iocoom, T1 caches, MSI
    directory, hop-counter NoC, lax_barrier) on the memory-FFT trace."""
    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, core="iocoom", shared_mem=True,
        clock_scheme="lax_barrier")))
    return Simulator(sc, fft_trace(tiles, points_per_tile=points,
                                   use_memory=True))


def _compile_run(sim, sharding, max_quanta=1_000_000):
    """The single-region program `Simulator.run()` dispatches."""
    return sim._get_runner(max_quanta).lower(
        _shapes(sim.state, sharding)).compile()


def _compile_host_batch(sim, sharding):
    """The bounded per-dispatch region `barrier_host` drives."""
    import jax.numpy as jnp

    return sim._hb_get_runner().lower(
        _shapes(sim.state, sharding),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=sharding),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)).compile()


def test_entry_step_compiles(one_chip):
    """`__graft_entry__.entry()`: the memoryless 64-tile quantum step."""
    import __graft_entry__ as ge

    fn, (state, qend) = ge.entry()
    compiled = jax.jit(fn).lower(
        _shapes(state, one_chip), _shapes(qend, one_chip)).compile()
    _fits(_report("entry-step-64", compiled))


def test_ref_default_16_compiles(one_chip):
    """The reference-default coherent program at 16 tiles — the same
    engine code as the 64-tile target, cut to tier-1 time."""
    sim = _ref_default(16, points=16)
    assert not sim.barrier_host
    _fits(_report("ref-default-16", _compile_run(sim, one_chip)))


def test_ungated_16_updates_the_l2_meta_store_in_place(one_chip):
    """What this guards against: a reader of a carried store that is not
    a data-dependence predecessor of the store's scatter.  XLA cannot
    order such a read before the in-place write and copies the whole
    store every iteration instead; `cache_array.scatter_row`'s contract
    ("the scatter is then the meta array's only remaining use and XLA
    updates the loop-carried buffer in place instead of copying it")
    holds only while a phase has ONE reader of the store it scatters
    into.  tests/test_inplace_stores.py asks the CPU backend, a proxy;
    this asks the compiler whose answer the served campaign cell pays
    for (PR 32: four `copy u32[4,64,1024,8]` an iteration were a third
    of `campaign64-dram`'s device time), of the gates-off program a
    campaign runs, at 16 tiles: no `while` body of it copies a
    [T, sets, ways] half of the int64 L2 meta store (the TPU splits
    it into two u32 halves; `l2_cloc` has the same dimensions at u8)."""
    from graphite_tpu.analysis.loop_copies import copies_of, loop_copies
    from graphite_tpu.trace.synthetic import memory_stress_trace

    sc = SimConfig(ConfigFile.from_string(config_text(
        16, core="iocoom", shared_mem=True, clock_scheme="lax_barrier")))
    sim = Simulator(sc, memory_stress_trace(16, n_accesses=8),
                    phase_gate=False, mem_gate_bytes=0)
    compiled = _compile_run(sim, one_chip)
    _fits(_report("ref-default-16-ungated", compiled))
    copies = loop_copies(compiled.as_text())
    meta = sim.state.mem.l2.meta
    assert str(meta.dtype) == "int64"
    halves = copies_of(copies, meta.shape, ("u32", "s64"))
    assert not halves, [c.line[:200] for c in halves]
    # what the iteration body still copies whole (PERF.md section 5): the
    # directory-entry store's relayout in front of the working-set gather
    print({"program": "ref-default-16-ungated", "in_loop_copies": sorted(
        (c.loop.depth, c.dtype, c.shape) for c in copies
        if c.size >= meta.size)})


def test_served_b4_16_gates_return_no_directory_store(one_chip):
    """The program a campaign's batch runs since ISSUE 36: B = 4 sims of
    the 16-tile reference-default target under `vmap`, the memory
    engine's phase gates on, their predicates OR-ed over the sim axis.
    Asked of the TPU compiler, because the served cell's `peak_hbm_gb`
    pays for the answer: all fourteen gates (seven of the memory engine,
    seven of the iteration's other blocks) reach it as `conditional`s -
    a batched predicate leaves none, only both branches and selects; no
    conditional returns a directory store (a branch output is a fresh
    buffer: the round-2 double-buffering, `engine.dir_store_avals`); and
    exactly the three directory-free phases return the two u32 halves of
    the int64 L2 meta store, which XLA must alias to the loop's carry
    for the batch's peak to hold - a fourth such region is a regression
    to measure (`_hand/memstat34.py`: PERF.md section 6, PR 36)."""
    from graphite_tpu.analysis.loop_copies import (
        conditionals, copies_of, loop_copies,
    )
    from graphite_tpu.memory.engine import PHASE_NAMES, dir_store_avals
    from graphite_tpu.sweep import SweepRunner
    from graphite_tpu.trace.synthetic import memory_stress_trace

    sc = SimConfig(ConfigFile.from_string(config_text(
        16, shared_mem=True, clock_scheme="lax_barrier")))
    runner = SweepRunner(
        sc, [memory_stress_trace(16, n_accesses=8)],
        [{"dram_latency_ns": lat} for lat in (60, 100, 140, 180)],
        shard_batch=False)
    assert runner.sim.params.mem.phase_gate
    assert not runner.sim.params.mem_gate
    compiled = runner._get_runner(1_000_000).lower(
        *(_shapes(t, one_chip) for t in runner.abstract_inputs())).compile()
    _fits(_report("served-b4-16", compiled))
    text = compiled.as_text()
    conds = conditionals(text)
    mem = [c for c in conds if "gt.mem." in c.op_name]
    assert len(conds) == 14 and len(mem) == 7, [c.op_name for c in conds]
    mem_state = runner.sim.state.mem
    for shape, _ in dir_store_avals(mem_state):
        hit = [c.op_name for c in conds if c.returns(shape)]
        assert not hit, (shape, hit)
    assert str(mem_state.l2.meta.dtype) == "int64"
    carriers = [c for c in conds
                if c.returns(mem_state.l2.meta.shape, ("u32", "s64"))]
    assert sorted(c.op_name.split("/")[-2] for c in carriers) == sorted(
        "gt.mem." + PHASE_NAMES[i] for i in (0, 3, 5)), [
            c.op_name for c in carriers]
    assert all(len(c.returns(mem_state.l2.meta.shape, ("u32", "s64"))) == 2
               for c in carriers)
    # ... and the engine's iteration body still copies neither half
    # (PR 32's guard, asked of the program a campaign runs now)
    body = loop_copies(text, under="gt.mem.requester/")
    halves = copies_of(body, mem_state.l2.meta.shape, ("u32", "s64"))
    assert not halves, [c.line[:200] for c in halves]
    _carries_the_int64_entry_store(runner.sim, text)


@pytest.mark.slow
def test_ref_default_64_compiles(one_chip):
    sim = _ref_default(64, points=64)
    compiled = _compile_run(sim, one_chip)
    _fits(_report("ref-default-64", compiled))
    _carries_the_int64_entry_store(sim, compiled.as_text())


def _hbh256(barrier_host):
    """Graduated config 3, 256-tile emesh_hop_by_hop RADIX at SPLASH-2's
    size: `hbh-256-radix` (benchmark/configs), target text and trace."""
    sc = SimConfig(ConfigFile.from_string(config_text(
        256, network="emesh_hop_by_hop")))
    return Simulator(sc, radix_trace(256, keys_per_tile=4096, radix=1024),
                     barrier_host=barrier_host)


@pytest.mark.slow
def test_hop_by_hop_256_compiles(one_chip):
    """The single region `tools/graduated.py` config 3 dispatches."""
    _fits(_report("hbh-256", _compile_run(_hbh256(None), one_chip)))


@pytest.mark.slow
def test_hbh256_radix_host_batch_compiles(one_chip):
    """The host-driven program the cell `hbh256-radix` dispatches: the
    dense contention under both of its scopes, no memory engine."""
    sim = _hbh256(True)
    assert sim.barrier_host and sim.params.mem is None
    compiled = _compile_host_batch(sim, one_chip)
    _fits(_report("hbh-256-host-batch", compiled))
    text = compiled.as_text()
    assert "gt.net.hbh.scan" in text and "gt.net.hbh.commit" in text


def _coh1024(barrier_host):
    sc = SimConfig(ConfigFile.from_string(config_text(
        1024, shared_mem=True, clock_scheme="lax_barrier")))
    return Simulator(sc, fft_trace(1024, points_per_tile=16,
                                   use_memory=True),
                     barrier_host=barrier_host)


def _flush_moves_the_staged_slots_alone(text, sharers, cap):
    """PR 43, of a staged program compiled for the chip: the flush is ONE
    `dir_stage_landing` call whose output IS the sharers store; nothing
    under `gt.mem.stage_flush` computes an array of the store's shape any
    more (the scatter-add's pass over it, `fusion u32[1048576,512]`), nor
    a row a table slot (`[T, C, DW*SW]`: the 201 MB gather and
    expansion); and no loop copies the store."""
    from graphite_tpu.analysis.loop_copies import copies_of, loop_copies

    T, DS, W = sharers
    kernel, = _landing_kernels(text, "dir_stage_landing",
                               "gt.mem.stage_flush")
    assert f"u32[{T * DS},{W}]" in kernel.split(" custom-call(")[0]
    assert "output_to_operand_aliasing={{}: (1, {})}" in kernel
    stores = (f"u32[{T * DS},{W}]", f"u32[{T},{DS},{W}]")
    for ln in text.splitlines():
        if "gt.mem.stage_flush" not in ln or " = " not in ln:
            continue
        head = ln.split(" = ", 1)[1][:80]
        assert not (head.startswith(stores) and " fusion(" in ln), ln[:300]
        assert not head.startswith(f"u32[{T},{cap},{W}]"), ln[:300]
    whole = copies_of(loop_copies(text), sharers, ("u32",))
    assert not whole, [c.line[:200] for c in whole]


def _entry_words_land_through_the_kernel(text, entry):
    """PR 45, of a program whose entry store is carried as u32 words,
    compiled for the chip: the home phases' plan lands through
    `dir_entry_landing`, under `gt.mem.entry_land`, whose output IS the
    store; the int64 scatter's five passes over a 64 MB half are gone
    from the program (its linearising copy, its fusion on the flat half,
    the reshape back), no loop copies the store, and memory-space
    assignment moves no piece of it (`slice-start` / `copy-start`: what
    it did to two separate halves)."""
    from graphite_tpu.analysis.loop_copies import (
        computations, copies_of, loop_copies, loops,
    )

    T, rows, DS = entry
    kernel, = _landing_kernels(text, "dir_entry_landing",
                               "gt.mem.entry_land")
    assert f"u32[{T * rows},{DS}]" in kernel.split(" custom-call(")[0]
    assert "output_to_operand_aliasing={{}: (5, {})}" in kernel
    half = T * DS * rows // 2
    gone = (f"u32[{half}]", f"u32[{2 * half}]",
            f"u32[{half // 8192},8,8,128]", f"u32[{half // 4096},8,8,128]",
            f"u32[{T},{rows // 2},{DS}]")
    stores = (f"u32[{T},{rows},{DS}]", f"u32[{T * rows},{DS}]")
    comps = computations(text)
    in_loops = set().union(*(lp.comps for lp in loops(comps).values()))
    assert any(kernel in comps[c] for c in in_loops)
    for ln in text.splitlines():
        if " = " in ln:
            head = ln.split(" = ", 1)[1].lstrip("(")[:60]
            assert not head.startswith(gone), ln[:300]
    for ln in (ln for c in in_loops for ln in comps[c] if " = " in ln):
        if ln.split(" = ", 1)[1].lstrip("(")[:60].startswith(stores):
            assert not any(op in ln for op in (
                " copy-start(", " slice-start(", " copy(", " fusion(",
                " reshape(")), ln[:300]
    whole = copies_of(loop_copies(text), entry, ("u32",))
    assert not whole, [c.line[:200] for c in whole]


def _carries_the_int64_entry_store(sim, text):
    """A program PR 45 and PR 46 leave as it was: the int64 store, no
    kernel; unstaged, so no staging overlay either."""
    d = sim.state.mem.directory
    assert str(d.entry.dtype) == "int64" and d.entry.ndim >= 3
    assert "dir_entry_landing" not in text
    assert "gt.mem.entry_land" not in text
    assert d.skey is None and "gt.mem.stage_overlay" not in text


def _overlay_fetches_a_way_a_phase(text, d):
    """PR 46, of a staged single-device program compiled for the chip:
    the loops hold no `u32[T*3*DW, SW]` - the eager overlay's gather of
    the staged value of EVERY way of the three set rows a lane, 49,152
    rows of 128 bytes and 0.56 ms an open iteration at 1,024 tiles -;
    what gathers out of the staging table `sval` is `[T, SW]` rows, under
    `gt.mem.stage_overlay` inside a home phase, one a phase; and the
    table is relaid no more often than the parent's program relays it
    (twice: round the flush's kernel, once a block)."""
    from graphite_tpu.analysis.loop_copies import computations, loops

    T, C, SW = d.sval.shape
    DW = d.sharers.shape[2] // SW
    comps = computations(text)
    in_loops = set().union(*(lp.comps for lp in loops(comps).values()))
    lines = [ln for c in in_loops if "fused_computation" not in c
             for ln in comps[c] if " = " in ln]
    table = f"u32[{T},{C},{SW}]"
    names = {ln.split(" = ", 1)[0].split()[-1] for ln in lines
             if ln.split(" = ", 1)[1].startswith(table)}
    fetches = []
    for ln in lines:
        head, rest = ln.split(" = ", 1)
        assert not rest.startswith(f"u32[{T * 3 * DW},{SW}]"), ln[:300]
        operands = rest.split(" fusion(", 1)[-1].split(")", 1)[0]
        if (" fusion(" in ln and "/gather" in ln
                and "gt.mem.stage_flush" not in ln   # the flush's sort
                and names & set(operands.split(", "))):
            fetches.append(ln)
    assert len(fetches) == 3, [f[:200] for f in fetches]
    for ln in fetches:
        assert ln.split(" = ", 1)[1].startswith(f"u32[{T},{SW}]"), ln[:300]
        assert "/gt.mem.stage_overlay/gather" in ln, ln[:400]
        assert any(f"/gt.mem.{p}/cond/" in ln for p in (
            "home_evict", "home_start", "home_finish")), ln[:400]
    relaid = [ln for ln in text.splitlines()
              if " = " in ln and ln.split(" = ", 1)[1].startswith(table)
              and " copy(" in ln]
    assert len(relaid) <= 2, [r[:200] for r in relaid]


@pytest.mark.parametrize("chips", [1, 4])
def test_stage_flush_alone_is_in_place_at_the_cells_shape(topo, chips):
    """One staging flush of `memstress1024-coh`'s directory, alone, as
    the engine runs it - `dir_stage_flush` under its in-place gate - on
    the cell's `u32[1024,1024,512]` sharers store (2.1 GB) and `[1024,
    96]` table, asked of the TPU compiler: the table is applied through
    the kernel (as an XLA scatter-add a flush is a pass over the store
    and two 201 MB temporaries, 19 ms), in place, under the flush's scope.
    Over four chips (`shard_map`, explicit `dir_stage=True` on a mesh:
    no cell) each device flushes its own 256 lanes the same way."""
    import jax.numpy as jnp

    from graphite_tpu.memory.engine import dir_stage_flush
    from graphite_tpu.memory.state import DirectoryArrays
    from graphite_tpu.obs.scopes import scope
    from graphite_tpu.parallel.mesh import TILE_AXIS, _shard_map

    T, DS, DW, SW, C = 1024, 1024, 16, 32, 96
    d = DirectoryArrays(
        entry=jax.ShapeDtypeStruct((T, DS, DW), jnp.int64),
        sharers=jax.ShapeDtypeStruct((T, DS, DW * SW), jnp.uint32),
        skey=jax.ShapeDtypeStruct((T, C), jnp.int32),
        sval=jax.ShapeDtypeStruct((T, C, SW), jnp.uint32),
        sn=jax.ShapeDtypeStruct((T,), jnp.int32))
    live = jax.ShapeDtypeStruct((), jnp.bool_)

    def flush(d, live):
        with scope("gt.mem.stage_flush"):
            return dir_stage_flush(d, live)

    if chips == 1:
        where = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices), (TILE_AXIS,))
        specs = (jax.tree.map(lambda _: P(TILE_AXIS), d), P())
        flush = _shard_map(flush, mesh=mesh, in_specs=specs,
                           out_specs=specs[0])
        where = jax.tree.map(lambda p: NamedSharding(mesh, p), specs,
                             is_leaf=lambda x: isinstance(x, P))
    compiled = jax.jit(flush, donate_argnums=0).lower(
        *_shapes((d, live), where)).compile()
    row = _report(f"stage-flush-alone-1024-x{chips}", compiled)
    Tl = T // chips
    # what the kernel reads beside the store: the table sorted by lane
    # and its slots' words repeated across a 128-word column (50 MB each
    # at the cell's shape; 134 MB of temporaries in all, where the XLA
    # flush's two `[T, C, DW*SW]` rows and its compare take 490 MB)
    assert chips > 1 or row["temp_bytes"] < 160 << 20, row
    _flush_moves_the_staged_slots_alone(compiled.as_text(),
                                        (Tl, DS, DW * SW), C)


@pytest.mark.slow
def test_coh_1024_host_batch_compiles(one_chip):
    """1024 tiles, full directory: the bounded region the selection
    rule picks (engine/step.barrier_host_batch); its staging flush goes
    through the kernel (PR 43)."""
    sim = _coh1024(None)
    assert sim.barrier_host
    compiled = _compile_host_batch(sim, one_chip)
    _fits(_report("coh-1024-host-batch", compiled))
    d = sim.state.mem.directory
    _flush_moves_the_staged_slots_alone(compiled.as_text(),
                                        d.sharers.shape, d.skey.shape[1])
    _entry_words_land_through_the_kernel(compiled.as_text(), d.entry.shape)
    _overlay_fetches_a_way_a_phase(compiled.as_text(), d)


def _shl2_memstress(tiles):
    """`shl2-mesi-1024-memstress` (benchmark/configs) at `tiles` tiles:
    the cell's target text and generator, host-driven as the cell."""
    from graphite_tpu.trace.synthetic import memory_stress_trace

    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, core="simple", shared_mem=True, clock_scheme="lax_barrier",
        network="emesh_hop_counter", protocol="pr_l1_sh_l2_mesi",
        scheme="full_map")))
    return Simulator(sc, memory_stress_trace(
        tiles, n_accesses=64, working_set_bytes=32768, write_fraction=0.4,
        shared_fraction=0.5, seed=7), barrier_host=True)


def _landing_kernels(text, name="dir_row_landing", scope="gt.mem.dir_apply"):
    """The compiled program's calls of a `memory/row_landing.py` kernel
    (the shared-L2 row landing, or `dir_stage_landing`), each named under
    its scope."""
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and name in ln]
    for ln in calls:
        assert f"/{scope}/" in ln.split("op_name=")[1], ln[:400]
    return calls


@pytest.mark.parametrize("chips", [1, 4])
def test_shl2_landing_alone_is_in_place_at_the_cells_shape(topo, chips):
    """One landing of `memstress1024-shl2`'s row plan, alone, as the
    engine lands it - `_dir_apply_rows` inside `engine._run_if`'s
    zero-or-one-trip loop - on the cell's embedded directory
    (`u32[1024,1024,256]` sharers = 1.07 GB, 1,024 plan rows), asked of
    the TPU compiler: the sharers plan lands through the row kernel
    (`memory/row_landing.py`; as an XLA scatter-add it cost a pass over
    the store, 3.3 ms), the kernel's output IS its operand, nothing
    copies the store, no conditional returns it (the choice of the
    lowering platform is resolved before the compiler), and the call is
    named under `gt.mem.dir_apply` for the benchmark's scope reader.
    Over four chips (`shard_map`, the tile axis split) each device lands
    its own 256 rows on its own quarter of the store the same way."""
    import jax.numpy as jnp

    from graphite_tpu.analysis.loop_copies import conditionals
    from graphite_tpu.memory.engine import _run_if
    from graphite_tpu.memory.engine_shl2 import ShL2Dir, _dir_apply_rows
    from graphite_tpu.parallel.mesh import TILE_AXIS, _shard_map
    from graphite_tpu.parallel.px import ParallelCtx

    T, S, W2, W = 1024, 1024, 8, 256
    d = ShL2Dir(word=jax.ShapeDtypeStruct((T, S, W2), jnp.int64),
                sharers=jax.ShapeDtypeStruct((T, S, W), jnp.uint32))
    rest = (jax.ShapeDtypeStruct((), jnp.bool_),
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((T, W2), jnp.int64),
            jax.ShapeDtypeStruct((T, W), jnp.uint32))
    px = ParallelCtx(axis=TILE_AXIS, n_dev=chips)

    def landing(d, live, *plan):
        return _run_if(live, lambda d: _dir_apply_rows(d, px, *plan), d)

    if chips == 1:
        where = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices), (TILE_AXIS,))
        specs = (jax.tree.map(lambda _: P(TILE_AXIS), d),) + (P(),) * 4
        landing = _shard_map(landing, mesh=mesh, in_specs=specs,
                             out_specs=specs[0])
        where = jax.tree.map(lambda p: NamedSharding(mesh, p), specs,
                             is_leaf=lambda x: isinstance(x, P))
    compiled = jax.jit(landing, donate_argnums=0).lower(
        *_shapes((d, *rest), where)).compile()
    row = _report(f"shl2-landing-alone-1024-x{chips}", compiled)
    Tl = T // chips
    assert row["alias_bytes"] == 4 * Tl * S * W + 8 * Tl * S * W2
    # (under `shard_map` this harness's loop holds two more blocks of
    # the store in `temp`, with the scatter-add as with the kernel)
    assert chips > 1 or row["temp_bytes"] < 64 << 20, row
    text = compiled.as_text()
    kernel, = _landing_kernels(text)
    assert f"u32[{Tl * S},{W}]" in kernel.split(" custom-call(")[0]
    assert "output_to_operand_aliasing={{}: (1, {})}" in kernel
    stores = (f"u32[{Tl * S},{W}]", f"u32[{Tl},{S},{W}]")
    for ln in text.splitlines():
        head = ln.split(" = ")[-1][:80]
        assert not (head.startswith(stores)
                    and (" copy(" in ln or " scatter(" in ln)), ln[:300]
    assert not [c for c in conditionals(text)
                if c.returns((Tl * S, W)) or c.returns((Tl, S, W))]


@pytest.mark.parametrize("tiles", [
    16, pytest.param(1024, marks=pytest.mark.slow)])
def test_shl2_mesi_1024_host_batch_compiles(one_chip, tiles):
    """The host-driven program of `memstress1024-shl2`, asked of the TPU
    compiler (the cell's own size is `slow`, as every 1024-tile compile
    here; 16 tiles is the same engine code in tier-1 time, under the
    whole-engine `mem_gate` that the cell's 1.29 GB state switches off).
    The shared-L2 engine's three home phases read the embedded directory
    inside their `lax.cond` and return a compact row plan; the plan lands
    outside (`gt.mem.dir_apply`), under the phase's predicate again but
    in a zero-or-one-trip `while_loop` (`engine._run_if`: a loop's carry
    is updated in place).  So: every one of the six phase gates
    reaches the compiler as a `conditional`; none returns the sharers
    store (a branch output is a fresh buffer: a store among them is
    double-buffered every iteration, `engine_shl2.dir_store_avals`), nor
    the directory's word store - what the three home phases do return of
    that shape is the two u32 halves of the SLICE's int64 meta store,
    which they update; and, at the cell's size, no loop of the program
    copies the sharers store whole (1.07 GB): the three landings update
    it in place, through the row kernel (PR 39)."""
    from graphite_tpu.analysis.loop_copies import (
        conditionals, copies_of, loop_copies,
    )
    from graphite_tpu.memory.engine_shl2 import (
        SHL2_PHASE_NAMES, dir_store_avals,
    )

    sim = _shl2_memstress(tiles)
    assert sim.barrier_host and sim.params.mem.phase_gate
    assert sim.params.mem_gate == (tiles < 1024)
    compiled = _compile_host_batch(sim, one_chip)
    _fits(_report(f"shl2-mesi-{tiles}-host-batch", compiled))
    text = compiled.as_text()
    phases = ["gt.mem." + p for p in SHL2_PHASE_NAMES]
    gates = {c.op_name.split("/")[-2]: c for c in conditionals(text)
             if c.op_name.count("/") and c.op_name.split("/")[-2] in phases}
    assert sorted(gates) == sorted(phases), sorted(gates)
    mem_state = sim.state.mem
    (word, _), (sharers, _) = dir_store_avals(mem_state)
    assert mem_state.l2.meta.shape == word
    assert str(mem_state.l2.meta.dtype) == "int64"
    for name, c in gates.items():
        # (at 16 tiles a sharers row is one word wide and the store has
        # the meta halves' shape and type: the count tells them apart)
        home = name.split(".")[-1].startswith("home_")
        stores = {a for shape in (word, sharers) for a in c.returns(shape)}
        assert stores <= {("u32", word)}, (name, stores)
        assert len(c.returns(word)) == (2 if home else 0), (name, c.outputs)
    copies = loop_copies(text)
    if not sim.params.mem_gate:
        # (under the whole-engine gate the small program's `gt.mem.base`
        # conditional returns every store: nothing to hold there)
        whole = copies_of(copies, sharers, ("u32",))
        assert not whole, [c.line[:200] for c in whole]
    # the landing's form follows the shapes: a lane-aligned sharers row
    # (256 words at 1,024 tiles) lands through the row kernel, three
    # landings a step, each under `gt.mem.dir_apply`; the 8-word row of
    # 16 tiles through the scatter-add, and no kernel is in the program
    kernels = _landing_kernels(text)
    assert len(kernels) == (3 if sharers[2] % 128 == 0 else 0), kernels
    print({"program": f"shl2-mesi-{tiles}-host-batch",
           "dir_apply_ops": text.count("gt.mem.dir_apply"),
           "largest_in_loop_copies": sorted(
               ((c.size, c.dtype, c.shape) for c in copies),
               reverse=True)[:6]})


def _canneal_dvfs(tiles):
    """`canneal-dvfs-1024` (benchmark/configs) at `tiles` tiles: two DVFS
    domains, power modelling on, the stepped canneal with the rotating
    schedule, host-driven as the cell."""
    from graphite_tpu.trace.benchmarks import canneal_trace

    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, dvfs=True, power=True,
        dvfs_domains="<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE> "
        "<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>")))
    return Simulator(
        sc, canneal_trace(tiles, footprint_lines=max(64, 15625 * tiles
                                                     // 1024),
                          swaps_per_tile=9, temperature_steps=5,
                          dvfs_schedule="rotate-levels"),
        barrier_host=True)


@pytest.mark.slow
@pytest.mark.parametrize("tiles", [16, 1024])
def test_canneal_dvfs_host_batch_compiles(one_chip, tiles):
    """The host-batch program of `canneal1024-dvfs` asked of the TPU
    compiler: the DVFS arm with the energy interval's close in it (int64
    products by per-level price gathers), and the core and memory
    blocks' divisions by a per-tile frequency.  Both sizes are `slow`:
    16 tiles take the TPU compiler 87 s here (16 MB of code), and tier-1
    has no such room (ISSUE 44: 1,355 s of 1,470)."""
    sim = _canneal_dvfs(tiles)
    assert sim.params.energy is not None and sim.params.dvfs.n_domains == 2
    compiled = _compile_host_batch(sim, one_chip)
    _fits(_report(f"canneal-dvfs-{tiles}-host-batch", compiled))
    assert "gt.energy" in compiled.as_text()
    if tiles == 1024:
        d = sim.state.mem.directory
        _entry_words_land_through_the_kernel(compiled.as_text(),
                                             d.entry.shape)
        _overlay_fetches_a_way_a_phase(compiled.as_text(), d)
    else:
        _carries_the_int64_entry_store(sim, compiled.as_text())


@pytest.mark.slow
def test_vfsweep_256_served_compiles(one_chip):
    """The program `vfsweep256-canneal` serves (PR 51): B = 4 sims of the
    256-tile DVFS + power target under `vmap`, its directory STAGED, one
    job a row of the V/f table.  Asked of the TPU compiler (173 s here;
    62.5 MB of code, 3.79 GB of temporaries beside 0.785 GB of arguments
    and 0.778 of outputs): it fits a chip, the DVFS arm and the thirteen
    other activity gates reach it as `conditional`s (a batched predicate
    would leave both branches and a select), the energy close is inside,
    and since PR 52 both private-L2 landing kernels are: the sims fold
    into 1,024 lanes (`row_landing._fold_sims`), so the entry words'
    flat `u32[33554432]` copy-scatter-reshape is gone."""
    from graphite_tpu.analysis.loop_copies import conditionals
    from graphite_tpu.sweep import SweepRunner
    from graphite_tpu.trace.benchmarks import canneal_trace

    sc = SimConfig(ConfigFile.from_string(config_text(
        256, shared_mem=True, dvfs=True, power=True,
        dvfs_domains="<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE> "
        "<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>")))
    runner = SweepRunner(
        sc, [canneal_trace(256, footprint_lines=15625, swaps_per_tile=9,
                           temperature_steps=5,
                           dvfs_schedule=f"level-{k}") for k in range(4)],
        shard_batch=False)
    assert runner.sim.params.mem.dir_stage_cap == 96
    compiled = runner._get_runner(1_000_000).lower(
        *(_shapes(t, one_chip) for t in runner.abstract_inputs())).compile()
    _fits(_report("vfsweep-256-served-b4", compiled))
    text = compiled.as_text()
    conds = conditionals(text)
    assert len(conds) == 14, [c.op_name for c in conds]
    assert sum("gt.dvfs/cond" in c.op_name for c in conds) == 1
    assert "gt.energy" in text and "gt.mem.stage_flush" in text
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 2, kernels
    assert sum("dir_entry_landing" in ln and "u32[32768,1024]" in ln
               for ln in kernels) == 1
    assert sum("dir_stage_landing" in ln and "u32[1048576,128]" in ln
               for ln in kernels) == 1
    assert "u32[33554432]" not in text


def _atac(tiles):
    """`atac-ackwise-1024-memstress` (benchmark/configs) at `tiles` tiles:
    `memory = atac` in clusters of 16 (of 4 at 16 tiles, where 16 would be
    one cluster and no packet would see a hub), ACKwise_4, the cell's
    generator, host-driven as the cell."""
    from graphite_tpu.trace.synthetic import memory_stress_trace

    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, network="atac", scheme="ackwise",
        max_hw_sharers=4, atac_cluster_size=16 if tiles >= 64 else 4)))
    return Simulator(
        sc, memory_stress_trace(
            tiles, n_accesses=32, working_set_bytes=32768,
            write_fraction=0.4, shared_fraction=0.5, seed=7),
        barrier_host=True)


@pytest.mark.slow
@pytest.mark.parametrize("tiles", [16, 1024])
def test_atac_host_batch_compiles(one_chip, tiles):
    """The host-batch program of `memstress1024-atac` asked of the TPU
    compiler: the hubs' `scatter_queue_delay` (lowered dense since PR 49:
    the M/G/1 arm's 32-step integer division once a QUEUE, one-hot
    selections and lane reductions over `[T, 2 C + 1]`, no scatter onto
    the queue table) twice a unicast and once a fan-out, and the fan-out's
    three `[T, T]` matrices (`zl`, `onet_pair`, the int64 `cumsum` of `rank`:
    8 MB each at 1,024 tiles) under the ACKwise broadcast arm.  Both
    sizes are `slow`, as `canneal_dvfs`'s."""
    sim = _atac(tiles)
    mp = sim.params.mem
    assert mp.net_atac is not None and mp.dir_type == "ackwise"
    compiled = _compile_host_batch(sim, one_chip)
    _fits(_report(f"atac-ackwise-{tiles}-host-batch", compiled))
    text = compiled.as_text()
    assert "gt.net.atac.hub" in text and "gt.net.atac.fanout" in text
    assert not [ln for ln in text.splitlines()
                if "gt.net.atac." in ln and (" scatter(" in ln
                                             or " gather(" in ln)]
    if tiles == 1024:
        # the staged directory holds under a scheme other than full_map:
        # the landing kernels follow the store's geometry, not the scheme
        assert mp.net_atac.n_clusters == 64 and mp.dir_stage_cap > 0
        d = sim.state.mem.directory
        _entry_words_land_through_the_kernel(text, d.entry.shape)
        _overlay_fetches_a_way_a_phase(text, d)


@pytest.mark.slow
def test_coh_1024_single_region_compiles(one_chip):
    """1024 tiles, full directory: the single-region lax_barrier
    program the selection rule avoids."""
    sim = _coh1024(False)
    compiled = _compile_run(sim, one_chip)
    _fits(_report("coh-1024-single-region", compiled))
    d = sim.state.mem.directory
    _flush_moves_the_staged_slots_alone(compiled.as_text(),
                                        d.sharers.shape, d.skey.shape[1])


@pytest.mark.slow
def test_shard_1024_compiles_over_four_chips(topo):
    """The tile-sharded 1024-tile coherence-stress program over a Mesh
    of the four described chips, with the shardings
    parallel/mesh.place_shard_map would place."""
    from graphite_tpu.parallel.mesh import (
        TILE_AXIS, make_shard_map_runner, shard_map_state_specs,
        shard_map_trace_specs,
    )

    mesh = Mesh(np.array(topo.devices), (TILE_AXIS,))
    sc, batch = coherence_stress_workload(1024, n_accesses=24)
    sim = Simulator(sc, batch)
    def named(specs):
        return jax.tree.map(lambda p: NamedSharding(mesh, p), specs,
                            is_leaf=lambda x: isinstance(x, P))

    st_shapes = _shapes(sim.state,
                        named(shard_map_state_specs(sim.state)))
    tr_shapes = _shapes(sim.device_trace,
                        named(shard_map_trace_specs(sim.device_trace)))
    runner = make_shard_map_runner(
        sim.params, sim.quantum_ps, 1_000_000, mesh, sim.state,
        sim.device_trace)
    compiled = runner.lower(st_shapes, tr_shapes).compile()
    _fits(_report("shard-1024-per-device", compiled))
    text = compiled.as_text()
    collectives = {op: text.count(f" {op}(") for op in (
        "all-gather", "all-reduce", "all-to-all", "collective-permute",
        "reduce-scatter", "all-gather-start", "all-reduce-start")}
    print({"program": "shard-1024", "collectives": collectives})
    assert sum(collectives.values()) > 0
