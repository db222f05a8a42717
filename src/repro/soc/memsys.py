"""Memory-system timing model for the ESP-like SoC (pure jnp, jit/vmap-able).

Models one accelerator invocation under each of the four coherence modes
(paper §2) in the presence of a concurrent set of other active accelerators,
producing the four monitor metrics of paper §4.1(4):

  total execution time, off-chip bytes, active cycles, communication cycles.

The model is analytical (service rates + queueing-style proportional
sharing), at the same granularity as the paper's traffic-generator
characterization.  It is calibrated to reproduce the qualitative findings of
paper §3:

  * small/medium warm workloads: cached modes avoid off-chip traffic
    entirely and win; NON_COH pays flush + cold DRAM reads and loses;
  * large workloads: caches thrash (LRU streaming over capacity), eviction
    writebacks double DRAM pressure, and NON_COH's long bursts win;
  * irregular patterns: word-granularity DMA is latency-bound, so cached
    modes win even at large sizes (paper Fig. 9, "irregular" SoC0);
  * concurrency: COH_DMA collapses worst (directory serialization at the
    LLC), NON_COH degrades least (paper Fig. 3: ~8x vs ~2.4x at 12 accs).

All shapes are static so the function nests under lax.scan/vmap in the
vectorized RL environment.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.modes import CoherenceMode
from repro.core.rewards import Measurement
from repro.core.vops import vsum
from repro.soc.accelerators import IRREGULAR, PF, STREAMING
from repro.soc.config import SoCConfig


class SoCStatic(NamedTuple):
    """Hashable scalar bundle of SoC + timing constants for jit closures."""

    n_cpus: float
    n_mem_tiles: float
    l2_bytes: float
    llc_slice_bytes: float
    line: float
    dram_lat: float
    dram_bw: float
    llc_hit_lat: float
    llc_bw: float
    l2_hit_lat: float
    l2_bw: float
    noc_hop_lat: float
    noc_bw: float
    driver_base: float
    tlb_per_page: float
    page_bytes: float
    flush_base: float
    flush_bw: float
    dir_lookup: float
    recall_lat: float
    mshr: float

    @classmethod
    def from_config(cls, soc: SoCConfig) -> "SoCStatic":
        t = soc.timings
        return cls(
            n_cpus=float(soc.n_cpus),
            n_mem_tiles=float(soc.n_mem_tiles),
            l2_bytes=float(soc.l2_bytes),
            llc_slice_bytes=float(soc.llc_slice_bytes),
            line=float(t.line_bytes),
            dram_lat=t.dram_lat,
            dram_bw=t.dram_bw,
            llc_hit_lat=t.llc_hit_lat,
            llc_bw=t.llc_bw,
            l2_hit_lat=t.l2_hit_lat,
            l2_bw=t.l2_bw,
            noc_hop_lat=t.noc_hop_lat,
            noc_bw=t.noc_bw,
            driver_base=t.driver_base,
            tlb_per_page=t.tlb_per_page,
            page_bytes=float(t.page_bytes),
            flush_base=t.flush_base,
            flush_bw=t.flush_bw,
            dir_lookup=t.dir_lookup,
            recall_lat=t.recall_lat,
            mshr=float(t.mshr_per_tile),
        )


_WORD = 8.0  # DMA word granularity (bytes) for irregular accesses

# Non-overlappable serial fraction between compute and communication phases.
_SERIAL_FRAC = 0.10
# Outstanding DMA bursts an ESP accelerator keeps in flight.
_DMA_OUTSTANDING = 4.0
# Fraction of LLC capacity consumed by CPU background traffic.
_CPU_LLC_RESERVE = 0.15
# LRU second-pass hit credit when the working set exceeds capacity.
_THRASH_HIT = 0.25


def warmth_after(mode, footprint, cache_capacity_bytes):
    """How warm a producer leaves its output for the next pipeline stage.

    NON_COH DMA lands data off-chip (cold); cached modes leave up to the
    hierarchy's capacity resident.  jnp-compatible; shared by the DES and
    the vectorized environment so the two paths cannot drift.
    """
    return jnp.where(
        mode == int(CoherenceMode.NON_COH_DMA), 0.0,
        jnp.minimum(1.0, cache_capacity_bytes
                    / jnp.maximum(footprint, 1.0)))


def _burst_bw(burst_bytes, lat, peak_bw, outstanding):
    """Effective bandwidth of latency-bound bursts with overlap."""
    t = lat + burst_bytes / peak_bw
    return jnp.minimum(peak_bw, outstanding * burst_bytes / t)


def dma_demand(mode, profile, footprint, s: SoCStatic, *, compute_scale=None):
    """Unconstrained (dram, llc) bytes/cycle an invocation asks for.

    Single-level approximation used to estimate contention caused by *other*
    accelerators; intentionally ignores their own contention (standard
    fixed-point shortcut).  ``compute_scale`` multiplies the compute cost
    per byte (a fault-injected slowdown lowers the demand the engine can
    generate); ``None`` keeps the exact pre-fault expression.
    """
    pattern = profile[PF.PATTERN]
    burst = jnp.where(pattern == IRREGULAR, _WORD, profile[PF.BURST])
    dma_bw = _burst_bw(burst, s.dram_lat, s.dram_bw, _DMA_OUTSTANDING)
    line_bw = _burst_bw(s.line, s.dram_lat + s.llc_hit_lat, s.dram_bw, s.mshr)
    cpb = profile[PF.COMPUTE] / profile[PF.ENGINES]
    if compute_scale is not None:
        cpb = cpb * compute_scale
    compute_bw = 1.0 / jnp.maximum(cpb, 1e-3)

    is_non_coh = mode == int(CoherenceMode.NON_COH_DMA)
    # Cached modes mostly stress the LLC; their DRAM demand is the miss
    # stream plus eviction writebacks.  Approximate miss ratio by footprint
    # vs one LLC slice.
    miss = jnp.clip(footprint / s.llc_slice_bytes, 0.05, 1.0)
    dirty = 1.0 - profile[PF.READ_FRAC]
    dram = jnp.where(is_non_coh,
                     jnp.minimum(dma_bw, compute_bw),
                     jnp.minimum(line_bw, compute_bw) * miss * (1.0 + dirty))
    llc = jnp.where(is_non_coh, 0.0, jnp.minimum(s.llc_bw, compute_bw))
    active = mode >= 0
    return jnp.where(active, dram, 0.0), jnp.where(active, llc, 0.0)


def invocation_perf(
    mode,
    profile,
    footprint,
    my_tiles,
    other_modes,
    other_profiles,
    other_footprints,
    other_tiles,
    warm_frac,
    s: SoCStatic,
    fault=None,
):
    """Timing + monitor metrics for one invocation. Returns (Measurement, aux).

    ``aux`` carries per-quantity breakdowns used by tests and by the
    hardware-monitor attribution model.

    This is the self-contained signature used by the DES: per-slot demand of
    the concurrent set is recomputed from ``other_profiles`` on every call.
    The vectorized environment caches that demand in its scan carry and
    calls :func:`invocation_perf_cached` instead.

    ``fault`` (optional ``repro.soc.faults.StepFault``) perturbs only *my*
    invocation; the concurrent set's demand stays the healthy steady-state
    estimate (the same fixed-point shortcut the contention model already
    takes).
    """
    od_dram, od_llc = jnp.vectorize(
        lambda m, p, fp: dma_demand(m, p, fp, s),
        signature="(),(k),()->(),()",
    )(other_modes, other_profiles, other_footprints)
    return invocation_perf_cached(
        mode, profile, footprint, my_tiles, other_modes, od_dram, od_llc,
        other_footprints, other_tiles, warm_frac, s, fault=fault)


def invocation_perf_cached(
    mode,
    profile,
    footprint,
    my_tiles,
    other_modes,
    other_dram_demand,
    other_llc_demand,
    other_footprints,
    other_tiles,
    warm_frac,
    s: SoCStatic,
    fault=None,
):
    """Fast-path variant of :func:`invocation_perf`.

    Takes the concurrent set's per-slot ``(dram, llc)`` bytes/cycle demand
    precomputed (``other_dram_demand``/``other_llc_demand``, each ``(T,)``)
    instead of the slots' profile rows.  A slot's demand depends only on its
    (mode, profile, footprint), which change exactly when that slot issues a
    new invocation — so the vectorized environment keeps demand in its scan
    carry, writes one slot per step, and skips the O(slots) recomputation
    (Alsop et al.: per-request-class demand is largely static).  Inactive
    slots (``other_modes < 0``) are masked here regardless of the demand
    value passed.  ``aux['demand_dram']``/``aux['demand_llc']`` return this
    invocation's own demand so the caller can cache it for its slot.

    ``fault`` is an optional ``repro.soc.faults.StepFault`` row: the DDR
    throttle rescales ``s.dram_bw`` (squeezing DMA, line-fill and the
    shared-bandwidth cap alike), the accelerator slowdown multiplies the
    compute cost per byte, the LLC spike adds foreign bytes/cycle of LLC
    load, and drop retries add backoff cycles to the driver overhead.
    ``fault=None`` (the default) is a trace-time branch that re-traces to
    the exact pre-fault program; a *neutral* row (1, 1, 0, 0) is a bitwise
    no-op on the arithmetic (``x * 1.0`` / ``x + 0.0`` on finite
    non-negative values), which is what the zero-``FaultSpec`` equivalence
    tests pin.
    """
    f32 = jnp.float32
    fault_scale = None
    if fault is not None:
        s = s._replace(dram_bw=s.dram_bw * fault.ddr_scale)
        fault_scale = fault.exec_scale
    footprint = jnp.maximum(jnp.asarray(footprint, f32), 1.0)
    n_my_tiles = jnp.maximum(jnp.sum(my_tiles.astype(f32)), 1.0)

    pattern = profile[PF.PATTERN]
    reuse = jnp.maximum(profile[PF.REUSE], 1.0)
    read_frac = profile[PF.READ_FRAC]
    afrac = jnp.where(pattern == IRREGULAR, profile[PF.ACCESS_FRAC], 1.0)
    in_place = profile[PF.IN_PLACE]
    compute_per_byte = profile[PF.COMPUTE] / jnp.maximum(profile[PF.ENGINES], 1.0)
    if fault is not None:
        compute_per_byte = compute_per_byte * fault.exec_scale

    read_bytes = footprint * read_frac * reuse      # line-granularity stream
    write_bytes = footprint * (1.0 - read_frac)
    dma_read_bytes = footprint * afrac * read_frac * reuse  # word granularity

    # ------------------------------------------------------------------
    # Contention from the concurrent set (proportional sharing per tile).
    # ------------------------------------------------------------------
    other_active = other_modes >= 0
    od_dram, od_llc = other_dram_demand, other_llc_demand

    overlap = jnp.sum(
        other_tiles.astype(f32) * my_tiles[None, :].astype(f32), axis=-1
    ) / jnp.maximum(jnp.sum(other_tiles.astype(f32), axis=-1), 1.0)

    my_dram_demand, my_llc_demand = dma_demand(
        mode, profile, footprint, s, compute_scale=fault_scale)
    dram_cap = s.dram_bw * n_my_tiles
    llc_cap = s.llc_bw * n_my_tiles

    dram_load = vsum(jnp.where(other_active, od_dram * overlap, 0.0))
    llc_load = vsum(jnp.where(other_active, od_llc * overlap, 0.0))
    if fault is not None:
        llc_load = llc_load + fault.llc_extra
    dram_slow = jnp.maximum(1.0, (dram_load + my_dram_demand) / dram_cap)
    llc_slow = jnp.maximum(1.0, (llc_load + my_llc_demand) / llc_cap)

    # LLC capacity share: my footprint vs all cached footprints on my tiles.
    other_cached = other_active & (other_modes != int(CoherenceMode.NON_COH_DMA))
    cached_fp = vsum(
        jnp.where(other_cached, other_footprints * overlap, 0.0)
    )
    llc_capacity = (
        s.llc_slice_bytes * n_my_tiles * (1.0 - _CPU_LLC_RESERVE)
    )
    my_llc_cap = llc_capacity * footprint / jnp.maximum(footprint + cached_fp, 1.0)

    # Directory serialization: other requesters holding the LLC controller.
    n_llc_users = vsum(jnp.where(other_cached, overlap, 0.0))

    # ------------------------------------------------------------------
    # Shared path bandwidths.
    # ------------------------------------------------------------------
    burst = jnp.where(pattern == IRREGULAR, _WORD, profile[PF.BURST])
    dma_bw = _burst_bw(burst, s.dram_lat + 2 * s.noc_hop_lat, s.dram_bw,
                       _DMA_OUTSTANDING) / dram_slow
    # Cached-mode line-fill path: NoC -> LLC (directory) -> DRAM -> back.
    line_fill_bw = _burst_bw(
        s.line, s.dram_lat + s.llc_hit_lat + 2 * s.noc_hop_lat,
        s.dram_bw, s.mshr,
    ) / dram_slow
    llc_hit_bw = jnp.minimum(s.llc_bw, s.noc_bw * n_my_tiles) / llc_slow

    # ------------------------------------------------------------------
    # Cache hit models.
    # ------------------------------------------------------------------
    warm_llc_bytes = warm_frac * jnp.minimum(footprint, my_llc_cap)
    fits_llc = footprint <= my_llc_cap
    cold_hit = warm_llc_bytes / footprint                       # first pass
    reuse_hit = jnp.where(fits_llc, 1.0, _THRASH_HIT * my_llc_cap / footprint)
    n_pass = jnp.maximum(reuse, 1.0)
    llc_hit_frac = (cold_hit + (n_pass - 1.0) * reuse_hit) / n_pass

    fits_l2 = footprint <= s.l2_bytes
    l2_reuse_hit = jnp.where(fits_l2, 1.0,
                             _THRASH_HIT * s.l2_bytes / footprint)
    l2_hit_frac = ((n_pass - 1.0) * l2_reuse_hit) / n_pass      # cold L2

    # ------------------------------------------------------------------
    # Overheads (driver, TLB preload, flushes) — paper §4.3 Actuate.
    # ------------------------------------------------------------------
    tlb = s.tlb_per_page * jnp.ceil(footprint / s.page_bytes)
    hierarchy = s.llc_slice_bytes * s.n_mem_tiles + s.n_cpus * s.l2_bytes
    full_flush_bytes = warm_frac * jnp.minimum(footprint, hierarchy)
    priv_flush_bytes = warm_frac * jnp.minimum(footprint, s.n_cpus * s.l2_bytes)
    ovh_base = s.driver_base + tlb
    # Nested wheres, not jnp.select: select lowers through an argmax over
    # bool conditions, which the Pallas TPU kernel cannot compile.
    ovh = jnp.where(
        mode == int(CoherenceMode.NON_COH_DMA),
        ovh_base + s.flush_base + full_flush_bytes / s.flush_bw,
        jnp.where(mode == int(CoherenceMode.LLC_COH_DMA),
                  ovh_base + s.flush_base + priv_flush_bytes / s.flush_bw,
                  ovh_base))
    if fault is not None:
        ovh = ovh + fault.retry_cycles

    # ------------------------------------------------------------------
    # Per-mode communication cycles and off-chip bytes.
    # ------------------------------------------------------------------
    # NON_COH_DMA: word-granularity DMA straight to DRAM.
    nc_offchip = dma_read_bytes + write_bytes + full_flush_bytes
    nc_comm = (dma_read_bytes + write_bytes) / jnp.maximum(dma_bw, 1e-3)

    # LLC paths (shared by the three cached modes).
    llc_miss_bytes = read_bytes * (1.0 - llc_hit_frac)
    llc_hit_bytes = read_bytes * llc_hit_frac
    dirty_frac = jnp.clip((1.0 - read_frac) + 0.25 * in_place, 0.0, 1.0)
    evict_bytes = jnp.where(fits_llc, 0.0, llc_miss_bytes * dirty_frac)
    llc_write_off = jnp.where(fits_llc, 0.0, write_bytes)

    def llc_path(dir_cost_per_line, extra_lat, fill_bw_scale):
        per_line = s.line / s.llc_bw + dir_cost_per_line
        ctl_bw = s.line / per_line / llc_slow
        hit_bw = jnp.minimum(llc_hit_bw, ctl_bw)
        fill = jnp.maximum(line_fill_bw * fill_bw_scale, 1e-3)
        comm = (
            llc_hit_bytes / jnp.maximum(hit_bw, 1e-3)
            + llc_miss_bytes / fill
            + write_bytes / jnp.maximum(ctl_bw, 1e-3)
            + evict_bytes / jnp.maximum(fill, 1e-3)
            + extra_lat
        )
        off = llc_miss_bytes + evict_bytes + llc_write_off
        return comm, off

    lc_comm, lc_off = llc_path(0.0, 0.0, 1.0)

    # COH_DMA: every beat takes a directory action; under sharing the
    # directory serializes (paper Fig. 3's 8x collapse): besides the lookup,
    # each line has a growing probability of needing an owner-check/recall
    # round trip as more cached-mode accelerators churn the same slice.
    # The churn only exists under cache PRESSURE — when the aggregate
    # cached working set fits the LLC, lines are stable and the directory
    # answers from steady state (no evictions/recalls), so the
    # user-scaling term is weighted by occupancy.
    pressure = jnp.clip(
        (cached_fp + footprint) / jnp.maximum(llc_capacity, 1.0), 0.0, 1.0)
    dir_cost = (
        s.dir_lookup * (1.0 + n_llc_users * pressure)
        + s.recall_lat * jnp.minimum(1.0, 0.15 * n_llc_users * pressure)
    )
    recall_bytes = warm_frac * jnp.minimum(footprint, s.n_cpus * s.l2_bytes)
    recall_cycles = (recall_bytes / s.line) * s.recall_lat / _DMA_OUTSTANDING
    cd_comm, cd_off = llc_path(dir_cost, recall_cycles, 1.0)

    # FULLY_COH: private-cache hits absorb traffic; misses traverse the
    # MESI directory.  Cold pass misses into LLC, reuse passes hit L2.
    l2_hit_bytes = read_bytes * l2_hit_frac
    l2_miss_bytes = read_bytes * (1.0 - l2_hit_frac)
    fc_llc_hit = l2_miss_bytes * llc_hit_frac
    fc_llc_miss = l2_miss_bytes * (1.0 - llc_hit_frac)
    fc_dirty = jnp.where(fits_l2, 0.0, l2_miss_bytes * dirty_frac * 0.5)
    per_line_fc = (s.line / s.llc_bw
                   + s.dir_lookup * (1.0 + 0.5 * n_llc_users * pressure))
    fc_ctl_bw = s.line / per_line_fc / llc_slow
    fc_evict = jnp.where(fits_llc, 0.0, fc_llc_miss * dirty_frac)
    fc_write_off = jnp.where(fits_llc, 0.0,
                             jnp.where(fits_l2, 0.0, write_bytes))
    fc_comm = (
        l2_hit_bytes / s.l2_bw
        + fc_llc_hit / jnp.maximum(jnp.minimum(llc_hit_bw, fc_ctl_bw), 1e-3)
        + fc_llc_miss / jnp.maximum(line_fill_bw, 1e-3)
        + (fc_dirty + fc_evict) / jnp.maximum(line_fill_bw, 1e-3)
        + jnp.where(fits_l2, write_bytes / s.l2_bw,
                    write_bytes / jnp.maximum(fc_ctl_bw, 1e-3))
    )
    fc_off = fc_llc_miss + fc_evict + fc_write_off

    def by_mode(nc, lc, cd, fc):
        return jnp.where(
            mode == int(CoherenceMode.NON_COH_DMA), nc,
            jnp.where(mode == int(CoherenceMode.LLC_COH_DMA), lc,
                      jnp.where(mode == int(CoherenceMode.COH_DMA), cd, fc)))

    comm_cycles = by_mode(nc_comm, lc_comm, cd_comm, fc_comm)
    offchip_bytes = by_mode(nc_offchip, lc_off, cd_off, fc_off)

    compute_cycles = compute_per_byte * footprint * reuse
    hi = jnp.maximum(compute_cycles, comm_cycles)
    lo = jnp.minimum(compute_cycles, comm_cycles)
    active_cycles = hi + _SERIAL_FRAC * lo      # pipelined overlap, §3
    exec_time = ovh + active_cycles

    m = Measurement(
        exec_time=exec_time,
        comm_cycles=comm_cycles,
        total_cycles=active_cycles,
        offchip_accesses=offchip_bytes / s.line,
        footprint=footprint,
    )
    aux = {
        "overhead": ovh,
        "compute_cycles": compute_cycles,
        "dram_slowdown": dram_slow,
        "llc_slowdown": llc_slow,
        "llc_hit_frac": llc_hit_frac,
        "offchip_bytes": offchip_bytes,
        # Own unconstrained demand — callers that cache per-slot demand
        # (soc.vecenv's scan carry) write these to this invocation's slot.
        "demand_dram": my_dram_demand,
        "demand_llc": my_llc_demand,
    }
    return m, aux
