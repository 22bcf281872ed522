"""Time-sampled statistics traces (`common/system/statistics_manager.cc`).

Reference behavior: a statistics thread wakes at every barrier quantum that
crosses the sampling interval and appends cache-line-replication and
network-utilization records to trace files (`statistics_thread.h:8-28`,
knobs `carbon_sim.cfg:394-411`).  Device-driven equivalent: the simulation
runs in bounded-quantum chunks sized to the sampling interval; between
chunks the sampler reads the state it needs in one batched device fetch and
appends records.  (Each sample costs one host↔device round trip — only
stats-enabled runs pay it, like the reference only pays when
[statistics_trace] enabled.)

Cache-line replication: from the L2 tag tensors directly — the number of
tiles caching each distinct line, as a histogram (the reference walks every
cache; here it is one np.unique over the tag arrays).
Network utilization: per-interval injection rate on the USER network
(exact, from packet counters) and the MEMORY network (message count
approximated from the protocol event counters).
Progress trace (`pin/progress_trace.cc`): per-tile clock/record progress
per sample.

Two backends (round 9):
 - `device`: the simulation runs as ONE compiled region recording a
   device-resident telemetry timeline (graphite_tpu/obs — zero host sync,
   the dispatch-tail fix), converted to the same `.trace` files in one
   post-run pass.  Covers the counter-derived statistics (network
   utilization); sample times are the quanta whose laggard clock crosses
   the sampling interval — the reference's statistics-thread wakeups.
 - `chunked`: the legacy host-driven sampling loop (one host<->device
   round trip PER SAMPLE).  Stays as the fallback for live-STATE
   snapshots the telemetry carry cannot afford: replication histograms
   over the full L2 tags, per-tile progress rows, energy sampling.
`backend="auto"` (the default) picks `device` exactly when every enabled
statistic is counter-derived.
"""

from __future__ import annotations

import os

import numpy as np

import jax


def chunk_quanta(sampling_interval_ns: int, quantum_ps: int) -> int:
    """Quanta per chunked-backend sample: the sampling interval floor-
    divided by the barrier quantum, never below one quantum (the
    reference's statistics thread wakes at barrier quanta only, so a
    sub-quantum interval degrades to per-quantum sampling).  Pinned by
    tests before the round-9 backend split."""
    return max(1, (int(sampling_interval_ns) * 1000) // int(quantum_ps))


class _StateEnergyView:
    """Live-state snapshot with the SimResults attributes
    `TileEnergyMonitor.tile_energy_j` consumes — lets the energy model
    run mid-simulation for periodic power sampling."""

    def __init__(self, sim):
        import dataclasses as _dc

        state = sim.state
        core = jax.device_get(state.core)
        self.clock_ps = np.asarray(core.clock_ps)
        self.instruction_count = np.asarray(core.instruction_count)
        self.bp_correct = np.asarray(core.bp_correct)
        self.bp_incorrect = np.asarray(core.bp_incorrect)
        self.packets_sent = np.asarray(
            jax.device_get(state.net.packets_sent))
        self.n_tiles = self.clock_ps.shape[0]
        if state.mem is not None:
            counters = jax.device_get(state.mem.counters)
            self.mem_counters = {
                f.name: np.asarray(getattr(counters, f.name))
                for f in _dc.fields(counters)}
        else:
            self.mem_counters = None


class StatisticsManager:
    """Drives a Simulator in sampling-interval chunks, writing traces."""

    def __init__(self, sim, output_dir: str = "stats",
                 backend: str = "auto"):
        cfg = sim.config.cfg
        self.sim = sim
        self.enabled = cfg.get_bool("statistics_trace/enabled", False)
        stats = cfg.get_string(
            "statistics_trace/statistics",
            "cache_line_replication, network_utilization")
        self.types = {s.strip() for s in stats.split(",") if s.strip()}
        self.sampling_interval_ns = cfg.get_int(
            "statistics_trace/sampling_interval", 10000)
        self.progress_enabled = cfg.get_bool("progress_trace/enabled", False)
        # periodic energy/power sampling (`[runtime_energy_modeling]`,
        # `carbon_sim.cfg:141-145`; `tile_energy_monitor.h:29`): rides the
        # same sampling loop; writes power.trace when power_trace/enabled
        self.power_enabled = cfg.get_bool(
            "runtime_energy_modeling/power_trace/enabled", False)
        if backend not in ("auto", "device", "chunked"):
            raise ValueError(f"unknown statistics backend {backend!r} "
                             "(expected 'auto', 'device' or 'chunked')")
        if backend == "device" and not self.device_supported():
            raise ValueError(
                "the device-timeline backend covers counter-derived "
                "statistics only (network_utilization under "
                "[statistics_trace]); replication/utilization histograms, "
                "per-tile progress rows and power sampling need live-state "
                "snapshots the telemetry carry cannot afford — use "
                "backend='chunked' (or 'auto') for those")
        self.backend = backend
        self.out_dir = output_dir
        self._files: dict = {}
        self._prev_user_packets = 0.0
        self._prev_mem_msgs = 0.0
        self._prev_sample_ns = 0
        self._energy_monitor = None
        self._prev_energy_j = None

    def device_supported(self) -> bool:
        """True when every ENABLED statistic is counter-derived, i.e.
        recordable from the carry by the device timeline: network
        utilization yes; replication/utilization histograms (full L2
        tag scans), per-tile progress rows and energy sampling no.
        Meshed and streamed sims always fall back to the chunked loop
        (the telemetry ring is not threaded through the multi-chip
        exchange or the streaming window loop)."""
        if self.sim.mesh is not None or self.sim.stream:
            return False
        if self.progress_enabled or self.power_enabled:
            return False
        if not self.enabled:
            # nothing to record at all — the chunked loop degenerates
            # to a plain run anyway, but there is no timeline to write
            return False
        unsupported = self.types - {"network_utilization"}
        return not unsupported and "network_utilization" in self.types

    # -- trace files (`openTraceFiles`) ---------------------------------
    def _file(self, name: str):
        if name not in self._files:
            os.makedirs(self.out_dir, exist_ok=True)
            self._files[name] = open(
                os.path.join(self.out_dir, f"{name}.trace"), "w")
        return self._files[name]

    def close(self) -> None:
        for f in self._files.values():
            f.close()
        self._files.clear()

    # -- samplers --------------------------------------------------------
    def replication_histogram(self) -> np.ndarray:
        """hist[k] = number of distinct lines cached by exactly k tiles
        (k = 1..n_tiles), from the L2 tag/state tensors."""
        ms = self.sim.state.mem
        if ms is None:
            return np.zeros(self.sim.params.n_tiles, np.int64)
        tags, state = jax.device_get((ms.l2.tags, ms.l2.state))
        valid = state != 0  # INVALID == 0
        lines = tags[valid]
        if lines.size == 0:
            return np.zeros(self.sim.params.n_tiles, np.int64)
        _, counts = np.unique(lines, return_counts=True)
        hist = np.bincount(counts, minlength=self.sim.params.n_tiles + 1)
        return hist[1:]

    def _memory_message_count(self, mem_counters) -> float:
        """Protocol messages ≈ 2x misses (req+rep) + 2x invalidations +
        evictions (approximation: the reference counts per-packet)."""
        if mem_counters is None:
            return 0.0
        return float(
            2 * mem_counters["l2_misses"].sum()
            + 2 * mem_counters["invalidations"].sum()
            + mem_counters["evictions"].sum())

    def _sim_time_ns(self) -> int:
        """Current simulated time: the laggard non-done tile's clock (the
        barrier boundary the quantum loop just crossed), or the max clock
        when all tiles are done."""
        done, clocks = jax.device_get(
            (self.sim.state.done, self.sim.state.core.clock_ps))
        pending = clocks[~done]
        t = pending.min() if pending.size else clocks.max()
        return int(t) // 1000

    def sample(self, time_ns: int) -> None:
        state = self.sim.state
        if not self.enabled:
            # [statistics_trace] enabled=false: only the independently
            # gated progress + power traces may write
            if self.power_enabled:
                self._sample_power(time_ns)
            if self.progress_enabled:
                clocks, idx = jax.device_get(
                    (state.core.clock_ps, state.core.idx))
                row = " ".join(
                    f"{c // 1000}/{i}" for c, i in zip(clocks, idx))
                self._file("progress").write(f"{time_ns} {row}\n")
            return
        if "cache_line_replication" in self.types and state.mem is not None:
            hist = self.replication_histogram()
            nz = np.flatnonzero(hist)
            row = " ".join(f"{k + 1}:{hist[k]}" for k in nz)
            self._file("cache_line_replication").write(
                f"{time_ns} {row}\n")
        if ("cache_line_utilization" in self.types and state.mem is not None
                and getattr(state.mem, "l2_util", None) is not None):
            # cumulative histogram of classified (departed) L2 lines by
            # total accesses, aggregated over tiles
            # (cache_line_utilization.h harvested at eviction/invalidation)
            hist = np.asarray(jax.device_get(
                state.mem.counters.line_util_hist)).sum(axis=0)
            row = " ".join(f"{k}:{int(v)}" for k, v in enumerate(hist))
            self._file("cache_line_utilization").write(
                f"{time_ns} {row}\n")
        if "network_utilization" in self.types:
            interval_ns = max(time_ns - self._prev_sample_ns, 1)
            sent, = jax.device_get((state.net.packets_sent,))
            total = float(sent.sum())
            delta = total - self._prev_user_packets
            self._prev_user_packets = total
            rate = delta / interval_ns / max(self.sim.params.n_tiles, 1)
            self._file("network_utilization_user").write(
                f"{time_ns} {rate:.6f}\n")
            if state.mem is not None:
                import dataclasses as _dc

                counters_h = jax.device_get(state.mem.counters)
                mc = {f.name: np.asarray(getattr(counters_h, f.name))
                      for f in _dc.fields(counters_h)}
                msgs = self._memory_message_count(mc)
                mdelta = msgs - self._prev_mem_msgs
                self._prev_mem_msgs = msgs
                mrate = mdelta / interval_ns / max(
                    self.sim.params.n_tiles, 1)
                f = self._file("network_utilization_memory")
                if f.tell() == 0:
                    # labeled as approximated: derived
                    # from protocol counters (~2x misses + 2x INVs +
                    # evictions), not per-interval packet counts
                    f.write("# approximated from protocol counters "
                            "(see _memory_message_count)\n")
                f.write(
                    f"{time_ns} {mrate:.6f}\n")
        self._prev_sample_ns = time_ns
        if self.power_enabled:
            self._sample_power(time_ns)
        if self.progress_enabled:
            clocks, idx = jax.device_get(
                (state.core.clock_ps, state.core.idx))
            row = " ".join(f"{c // 1000}/{i}" for c, i in zip(clocks, idx))
            self._file("progress").write(f"{time_ns} {row}\n")

    def _sample_power(self, time_ns: int) -> None:
        """Periodic per-tile energy/power from the live counters
        (`TileEnergyMonitor::periodicallyCollectEnergy`): total energy so
        far per tile, and average power over the elapsed interval; one
        `time_ns  e0:p0 e1:p1 ...` row per sample in power.trace."""
        from graphite_tpu.power.interface import TileEnergyMonitor

        snap = _StateEnergyView(self.sim)
        if self._energy_monitor is None:
            self._energy_monitor = TileEnergyMonitor(self.sim, snap)
        else:
            self._energy_monitor.results = snap
        T = self.sim.params.n_tiles
        energies = np.asarray(
            [self._energy_monitor.tile_energy_j(t)["total"]
             for t in range(T)])
        if self._prev_energy_j is None:
            self._prev_energy_j = np.zeros(T)
            prev_t = 0
        else:
            prev_t = self._power_prev_t
        dt_s = max(time_ns - prev_t, 1) * 1e-9
        power_w = (energies - self._prev_energy_j) / dt_s
        self._prev_energy_j = energies
        self._power_prev_t = time_ns
        row = " ".join(f"{e:.4e}:{p:.4e}"
                       for e, p in zip(energies, power_w))
        self._file("power").write(f"{time_ns} {row}\n")

    # -- sampled run (`statistics_thread` + barrier wakeups) -------------
    def run(self, max_samples: int = 100000):
        """Run the simulation to completion, sampling every interval.

        Requires lax_barrier (the reference demands the same:
        `carbon_sim.cfg:397`).  Backend dispatch: `device` records the
        timeline inside ONE compiled run (zero host sync) and converts
        it post-run; `chunked` drives the legacy host loop — chunk size
        is sampling_interval / barrier quantum (`chunk_quanta`), so
        samples land on quantum boundaries exactly as the reference's
        statistics thread does.  `auto` picks `device` when every
        enabled statistic is counter-derived.
        """
        sim = self.sim
        if sim.quantum_ps is None:
            raise ValueError(
                "statistics sampling needs clock_skew_management/scheme = "
                "lax_barrier (reference requirement)")
        if self.backend == "device" or (self.backend == "auto"
                                        and self.device_supported()):
            return self._run_device(max_samples)
        quanta_per_sample = chunk_quanta(self.sampling_interval_ns,
                                         sim.quantum_ps)
        total_quanta = 0
        done = False
        for s in range(max_samples):
            done, nq = sim.run_chunk(int(quanta_per_sample))
            total_quanta += nq
            # timestamp from the device clocks: the loop skips empty
            # quanta, so iteration count is NOT simulated time
            self.sample(time_ns=self._sim_time_ns())
            if done:
                break
        self.close()
        if not done:
            raise RuntimeError(
                f"statistics run truncated: {max_samples} samples "
                f"({total_quanta} quanta) without completing")
        return sim._results_from_state(total_quanta)

    # -- device-timeline backend (round 9, graphite_tpu/obs) -------------
    def _run_device(self, max_samples: int):
        """One compiled telemetry-recording run, then a post-run pass
        converting the timeline into the same `.trace` files the chunked
        sampler writes — no per-sample host round trips."""
        from graphite_tpu.obs import TelemetrySpec

        sim = self.sim
        series = ["time_ps", "packets_sent"]
        if sim.state.mem is not None:
            series += ["l2_misses", "invalidations", "evictions"]
        sim.attach_telemetry(TelemetrySpec(
            sample_interval_ps=self.sampling_interval_ns * 1000,
            n_samples=max_samples, series=series))
        results = sim.run()
        self.write_timeline(results.telemetry)
        self.close()
        return results

    def write_timeline(self, tl) -> None:
        """Convert a recorded `obs.Timeline` into the chunked sampler's
        `.trace` file formats (same rows, same normalization: per-ns
        per-tile rates against the previous sample's timestamp)."""
        if tl.wrapped:
            raise ValueError(
                "telemetry ring wrapped: the first "
                f"{tl.n_total - len(tl)} sample(s) were overwritten — "
                "raise max_samples (the ring depth) to cover the run")
        T = max(self.sim.params.n_tiles, 1)
        have_mem = all(s in tl.series
                       for s in ("l2_misses", "invalidations", "evictions"))
        prev_ns = 0
        for i in range(len(tl)):
            t_ns = int(tl.time_ns[i])
            interval_ns = max(t_ns - prev_ns, 1)
            prev_ns = t_ns
            if "network_utilization" not in self.types or not self.enabled:
                continue
            rate = float(tl.col("packets_sent")[i]) / interval_ns / T
            self._file("network_utilization_user").write(
                f"{t_ns} {rate:.6f}\n")
            if have_mem:
                # the chunked backend's approximation applied to the
                # recorded DELTAS (the formula is linear, so
                # delta-of-approx == approx-of-delta)
                mdelta = self._memory_message_count(
                    {k: tl.col(k)[i:i + 1]
                     for k in ("l2_misses", "invalidations", "evictions")})
                f = self._file("network_utilization_memory")
                if f.tell() == 0:
                    f.write("# approximated from protocol counters "
                            "(see _memory_message_count)\n")
                f.write(f"{t_ns} {mdelta / interval_ns / T:.6f}\n")
