"""Plain reference of the simulated SoC: the yardstick that decides ``correct``.

A self-contained, straightforward ``jax.numpy`` statement of what one
simulated accelerator invocation does under the lockstep model -- sense
(paper Table 3), epsilon-greedy select, the memory-system timing model,
the multi-objective reward and the tabular Q update -- plus the episode,
training, evaluation and serving loops built from it.  It imports nothing
of the program under test: it reads plain data (SoC sizes, accelerator
profile fields, the applications' invocation lists) and draws its noise
from the same PRNG keys, so it sees the same inputs as the program.

The formulas follow the program's published semantics as they stood when
this benchmark was defined; later changes to the program are judged
against them.  Inside the per-invocation loops a row or element is read
and written through a one-hot select (:func:`pick`, :func:`put`): it
reads the same value as ``x[i]`` and keeps XLA on a TPU off serialized
gathers and scatters across a batch of agents.

``rnd`` hooks let the precision control round every float the step carries
from one invocation to the next (and each trace row) to bfloat16.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
I32 = jnp.int32

NON_COH, LLC_COH, COH_DMA, FULLY_COH = 0, 1, 2, 3
N_MODES = 4
N_STATES = 243
EPS0, ALPHA0, Q_INIT, REOPEN_FRAC = 0.5, 0.25, 1.0, 0.5
CYCLE_TIME = 1e-8
EXTRA_SMALL = 4 * 1024          # manual policy: always fully coherent below
STRIPE_BYTES = 256 << 10        # memory-tile striping granularity

# Accelerator profile columns.
P_PATTERN, P_BURST, P_COMPUTE, P_REUSE, P_READ, P_STRIDE, P_AFRAC, \
    P_INPLACE, P_ENGINES = range(9)
IRREGULAR = 2

# Timing-model constants.
WORD, SERIAL_FRAC, DMA_OUTSTANDING = 8.0, 0.10, 4.0
CPU_LLC_RESERVE, THRASH_HIT = 0.15, 0.25
NEG = np.float32(-3.4e38)
BIG = np.float32(3.4e38)
TINY = np.float32(1e-12)
MAX_RETRIES = 3
NO_DEADLINE = np.float32(1e30)

STATIC_FIELDS = ("n_cpus", "n_mem_tiles", "l2_bytes", "llc_slice_bytes",
                 "line", "dram_lat", "dram_bw", "llc_hit_lat", "llc_bw",
                 "l2_hit_lat", "l2_bw", "noc_hop_lat", "noc_bw",
                 "driver_base", "tlb_per_page", "page_bytes", "flush_base",
                 "flush_bw", "dir_lookup", "recall_lat", "mshr")
Static = NamedTuple("Static", [(f, object) for f in STATIC_FIELDS])


def identity(x):
    return x


def to_bf16(x):
    """The precision control's rounding: each float32 to the nearest
    bfloat16 (ties to even), by clearing the low 16 bits.  Done on the bit
    pattern because a compiler that may keep excess precision is free to
    drop an f32 -> bf16 -> f32 round trip."""
    if x.dtype != F32:
        return x
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) & np.uint32(
        0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, F32)


def pick(a, i):
    """``a[i]`` along the leading axis, as a one-hot select and sum."""
    hot = (jnp.arange(a.shape[0]) == i).reshape((-1,) + (1,) * (a.ndim - 1))
    if a.dtype == jnp.bool_:
        return jnp.any(hot & a, axis=0)
    return jnp.sum(jnp.where(hot, a, jnp.zeros((), a.dtype)), axis=0)


def put(a, i, v):
    """``a.at[i].set(v)`` along the leading axis, as a one-hot select."""
    hot = jnp.arange(a.shape[0]) == i
    return jnp.where(hot.reshape((-1,) + (1,) * (a.ndim - 1)), v, a)


# ------------------------------------------------------------------ data
def static_vector(soc: dict) -> np.ndarray:
    """The SoC's 21 timing scalars, in :data:`STATIC_FIELDS` order."""
    t = soc["timings"]
    vals = dict(
        n_cpus=soc["n_cpus"], n_mem_tiles=soc["n_mem_tiles"],
        l2_bytes=soc["l2_bytes"], llc_slice_bytes=soc["llc_slice_bytes"],
        line=t["line_bytes"], dram_lat=t["dram_lat"], dram_bw=t["dram_bw"],
        llc_hit_lat=t["llc_hit_lat"], llc_bw=t["llc_bw"],
        l2_hit_lat=t["l2_hit_lat"], l2_bw=t["l2_bw"],
        noc_hop_lat=t["noc_hop_lat"], noc_bw=t["noc_bw"],
        driver_base=t["driver_base"], tlb_per_page=t["tlb_per_page"],
        page_bytes=t["page_bytes"], flush_base=t["flush_base"],
        flush_bw=t["flush_bw"], dir_lookup=t["dir_lookup"],
        recall_lat=t["recall_lat"], mshr=t["mshr_per_tile"])
    return np.asarray([float(vals[f]) for f in STATIC_FIELDS], np.float32)


def profile_rows(profiles) -> np.ndarray:
    """(n_accs, 9) float32 profile matrix from accelerator profile fields."""
    return np.asarray([[p.pattern, p.burst_bytes, p.compute_per_byte,
                        p.reuse, p.read_frac, p.stride_bytes, p.access_frac,
                        1.0 if p.in_place else 0.0, p.engines]
                       for p in profiles], np.float32)


def mode_masks(n_accs: int, no_private_cache) -> np.ndarray:
    m = np.ones((n_accs, N_MODES), bool)
    for i in no_private_cache:
        m[i, FULLY_COH] = False
    return m


def _stripe(rng, n_tiles: int, footprint: float) -> np.ndarray:
    span = int(min(n_tiles, max(1, int(np.ceil(footprint / STRIPE_BYTES)))))
    start = int(rng.integers(0, n_tiles))
    mask = np.zeros(n_tiles, bool)
    for k in range(span):
        mask[(start + k) % n_tiles] = True
    return mask


def schedule_rows(app, n_tiles: int, seed: int) -> dict:
    """An application as round-major invocation rows (the lockstep model).

    Round ``r`` holds each thread's ``r``-th invocation; a row's
    ``others`` marks the threads concurrently active with it (threads
    before it in round ``r``, threads after it still in round ``r-1``)."""
    rng = np.random.default_rng(seed)
    t_max = max((len(ph.threads) for ph in app.phases), default=1)
    rows = []
    for p, phase in enumerate(app.phases):
        progs = [[inv for _ in range(th.loops) for inv in th.chain]
                 for th in phase.threads]
        started = [False] * len(progs)
        for r in range(max((len(q) for q in progs), default=0)):
            for t, prog in enumerate(progs):
                if r >= len(prog):
                    continue
                inv = prog[r]
                tiles = _stripe(rng, n_tiles, inv.footprint)
                others = np.zeros(t_max, bool)
                for j, pj in enumerate(progs):
                    if j < t:
                        others[j] = r < len(pj)
                    elif j > t:
                        others[j] = r >= 1 and (r - 1) < len(pj)
                rows.append((inv.acc_id, inv.footprint, tiles, t, p,
                             not started[t], others))
                started[t] = True
    return dict(
        acc_id=np.asarray([r[0] for r in rows], np.int32),
        footprint=np.asarray([r[1] for r in rows], np.float32),
        tiles=np.stack([r[2] for r in rows]),
        thread=np.asarray([r[3] for r in rows], np.int32),
        phase_id=np.asarray([r[4] for r in rows], np.int32),
        fresh=np.asarray([r[5] for r in rows], bool),
        others=np.stack([r[6] for r in rows]),
        valid=np.ones(len(rows), bool),
        n_phases=len(app.phases), n_threads=t_max)


def pad_rows(sched: dict, n_steps: int, n_threads: int, n_tiles: int):
    """Pad to a common shape: inert ``valid=False`` rows at the tail."""
    def pad(a, axis, n, fill):
        w = [(0, 0)] * a.ndim
        w[axis] = (0, n - a.shape[axis])
        return np.pad(a, w, constant_values=fill)
    s = sched
    return dict(
        acc_id=pad(s["acc_id"], 0, n_steps, 0),
        footprint=pad(s["footprint"], 0, n_steps, 1.0),
        tiles=pad(pad(s["tiles"], 1, n_tiles, False), 0, n_steps, False),
        thread=pad(s["thread"], 0, n_steps, 0),
        phase_id=pad(s["phase_id"], 0, n_steps, 0),
        fresh=pad(s["fresh"], 0, n_steps, True),
        others=pad(pad(s["others"], 1, n_threads, False), 0, n_steps, False),
        valid=pad(s["valid"], 0, n_steps, False))


class Lanes(NamedTuple):
    """K SoCs padded to common accelerator count (leading lane axis)."""

    static: np.ndarray   # (K, 21)
    pmat: np.ndarray     # (K, n_accs, 9)
    masks: np.ndarray    # (K, n_accs, 4)


def make_lanes(socs, profiles_per_soc) -> Lanes:
    n = max(s["n_accs"] for s in socs)
    pm = np.zeros((len(socs), n, 9), np.float32)
    mk = np.ones((len(socs), n, N_MODES), bool)
    for k, (s, prof) in enumerate(zip(socs, profiles_per_soc)):
        pm[k, :s["n_accs"]] = profile_rows(prof)
        mk[k, :s["n_accs"]] = mode_masks(s["n_accs"], s["no_private_cache"])
    return Lanes(np.stack([static_vector(s) for s in socs]), pm, mk)


def stack_lanes(scheds) -> tuple[dict, list[int]]:
    """Pad per-lane schedule dicts to one shape; returns (stacked, n_real)."""
    s_max = max(len(s["acc_id"]) for s in scheds)
    t_max = max(s["others"].shape[1] for s in scheds)
    n_tiles = max(s["tiles"].shape[1] for s in scheds)
    padded = [pad_rows(s, s_max, t_max, n_tiles) for s in scheds]
    return ({k: np.stack([p[k] for p in padded]) for k in padded[0]},
            [len(s["acc_id"]) for s in scheds])


# ---------------------------------------------------------- timing model
def _burst_bw(burst, lat, peak, outstanding):
    t = lat + burst / peak
    return jnp.minimum(peak, outstanding * burst / t)


def _demand(mode, prof, fp, s: Static):
    burst = jnp.where(prof[P_PATTERN] == IRREGULAR, WORD, prof[P_BURST])
    dma_bw = _burst_bw(burst, s.dram_lat, s.dram_bw, DMA_OUTSTANDING)
    line_bw = _burst_bw(s.line, s.dram_lat + s.llc_hit_lat, s.dram_bw, s.mshr)
    cpb = prof[P_COMPUTE] / prof[P_ENGINES]
    compute_bw = 1.0 / jnp.maximum(cpb, 1e-3)
    nc = mode == NON_COH
    miss = jnp.clip(fp / s.llc_slice_bytes, 0.05, 1.0)
    dirty = 1.0 - prof[P_READ]
    dram = jnp.where(nc, jnp.minimum(dma_bw, compute_bw),
                     jnp.minimum(line_bw, compute_bw) * miss * (1.0 + dirty))
    llc = jnp.where(nc, 0.0, jnp.minimum(s.llc_bw, compute_bw))
    active = mode >= 0
    return jnp.where(active, dram, 0.0), jnp.where(active, llc, 0.0)


def invocation_timing(mode, prof, fp, my_tiles, o_modes, o_dram, o_llc,
                      o_fps, o_tiles, warm, s: Static):
    """(exec, comm, active cycles, off-chip lines, own dram/llc demand)."""
    fp = jnp.maximum(fp.astype(F32), 1.0)
    n_my = jnp.maximum(jnp.sum(my_tiles.astype(F32)), 1.0)
    pattern = prof[P_PATTERN]
    reuse = jnp.maximum(prof[P_REUSE], 1.0)
    rf = prof[P_READ]
    afrac = jnp.where(pattern == IRREGULAR, prof[P_AFRAC], 1.0)
    cpb = prof[P_COMPUTE] / jnp.maximum(prof[P_ENGINES], 1.0)
    read_b = fp * rf * reuse
    write_b = fp * (1.0 - rf)
    dma_read_b = fp * afrac * rf * reuse

    o_act = o_modes >= 0
    ot = o_tiles.astype(F32)
    overlap = (jnp.sum(ot * my_tiles[None, :].astype(F32), axis=-1)
               / jnp.maximum(jnp.sum(ot, axis=-1), 1.0))
    my_dram, my_llc = _demand(mode, prof, fp, s)
    dram_load = jnp.sum(jnp.where(o_act, o_dram * overlap, 0.0))
    llc_load = jnp.sum(jnp.where(o_act, o_llc * overlap, 0.0))
    dram_slow = jnp.maximum(1.0, (dram_load + my_dram) / (s.dram_bw * n_my))
    llc_slow = jnp.maximum(1.0, (llc_load + my_llc) / (s.llc_bw * n_my))

    o_cached = o_act & (o_modes != NON_COH)
    cached_fp = jnp.sum(jnp.where(o_cached, o_fps * overlap, 0.0))
    llc_cap_all = s.llc_slice_bytes * n_my * (1.0 - CPU_LLC_RESERVE)
    my_llc_cap = llc_cap_all * fp / jnp.maximum(fp + cached_fp, 1.0)
    n_users = jnp.sum(jnp.where(o_cached, overlap, 0.0))

    burst = jnp.where(pattern == IRREGULAR, WORD, prof[P_BURST])
    dma_bw = _burst_bw(burst, s.dram_lat + 2 * s.noc_hop_lat, s.dram_bw,
                       DMA_OUTSTANDING) / dram_slow
    fill_bw = _burst_bw(s.line, s.dram_lat + s.llc_hit_lat
                        + 2 * s.noc_hop_lat, s.dram_bw, s.mshr) / dram_slow
    hit_bw0 = jnp.minimum(s.llc_bw, s.noc_bw * n_my) / llc_slow

    warm_llc = warm * jnp.minimum(fp, my_llc_cap)
    fits_llc = fp <= my_llc_cap
    cold_hit = warm_llc / fp
    reuse_hit = jnp.where(fits_llc, 1.0, THRASH_HIT * my_llc_cap / fp)
    n_pass = jnp.maximum(reuse, 1.0)
    llc_hit = (cold_hit + (n_pass - 1.0) * reuse_hit) / n_pass
    fits_l2 = fp <= s.l2_bytes
    l2_reuse = jnp.where(fits_l2, 1.0, THRASH_HIT * s.l2_bytes / fp)
    l2_hit = ((n_pass - 1.0) * l2_reuse) / n_pass

    tlb = s.tlb_per_page * jnp.ceil(fp / s.page_bytes)
    hierarchy = s.llc_slice_bytes * s.n_mem_tiles + s.n_cpus * s.l2_bytes
    full_flush = warm * jnp.minimum(fp, hierarchy)
    priv_flush = warm * jnp.minimum(fp, s.n_cpus * s.l2_bytes)
    base = s.driver_base + tlb
    ovh = jnp.where(mode == NON_COH,
                    base + s.flush_base + full_flush / s.flush_bw,
                    jnp.where(mode == LLC_COH,
                              base + s.flush_base + priv_flush / s.flush_bw,
                              base))

    nc_off = dma_read_b + write_b + full_flush
    nc_comm = (dma_read_b + write_b) / jnp.maximum(dma_bw, 1e-3)

    miss_b = read_b * (1.0 - llc_hit)
    hit_b = read_b * llc_hit
    dirty = jnp.clip((1.0 - rf) + 0.25 * prof[P_INPLACE], 0.0, 1.0)
    evict_b = jnp.where(fits_llc, 0.0, miss_b * dirty)
    llc_write_off = jnp.where(fits_llc, 0.0, write_b)

    def llc_path(dir_cost, extra, scale):
        per_line = s.line / s.llc_bw + dir_cost
        ctl_bw = s.line / per_line / llc_slow
        hit_bw = jnp.minimum(hit_bw0, ctl_bw)
        fill = jnp.maximum(fill_bw * scale, 1e-3)
        comm = (hit_b / jnp.maximum(hit_bw, 1e-3) + miss_b / fill
                + write_b / jnp.maximum(ctl_bw, 1e-3)
                + evict_b / jnp.maximum(fill, 1e-3) + extra)
        return comm, miss_b + evict_b + llc_write_off

    lc_comm, lc_off = llc_path(0.0, 0.0, 1.0)
    pressure = jnp.clip((cached_fp + fp) / jnp.maximum(llc_cap_all, 1.0),
                        0.0, 1.0)
    dir_cost = (s.dir_lookup * (1.0 + n_users * pressure)
                + s.recall_lat * jnp.minimum(1.0, 0.15 * n_users * pressure))
    recall_b = warm * jnp.minimum(fp, s.n_cpus * s.l2_bytes)
    recall_cyc = (recall_b / s.line) * s.recall_lat / DMA_OUTSTANDING
    cd_comm, cd_off = llc_path(dir_cost, recall_cyc, 1.0)

    l2_hit_b = read_b * l2_hit
    l2_miss_b = read_b * (1.0 - l2_hit)
    fc_hit = l2_miss_b * llc_hit
    fc_miss = l2_miss_b * (1.0 - llc_hit)
    fc_dirty = jnp.where(fits_l2, 0.0, l2_miss_b * dirty * 0.5)
    per_line_fc = s.line / s.llc_bw + s.dir_lookup * (
        1.0 + 0.5 * n_users * pressure)
    fc_ctl = s.line / per_line_fc / llc_slow
    fc_evict = jnp.where(fits_llc, 0.0, fc_miss * dirty)
    fc_write_off = jnp.where(fits_llc, 0.0, jnp.where(fits_l2, 0.0, write_b))
    fc_comm = (l2_hit_b / s.l2_bw
               + fc_hit / jnp.maximum(jnp.minimum(hit_bw0, fc_ctl), 1e-3)
               + fc_miss / jnp.maximum(fill_bw, 1e-3)
               + (fc_dirty + fc_evict) / jnp.maximum(fill_bw, 1e-3)
               + jnp.where(fits_l2, write_b / s.l2_bw,
                           write_b / jnp.maximum(fc_ctl, 1e-3)))
    fc_off = fc_miss + fc_evict + fc_write_off

    def by_mode(nc, lc, cd, fc):
        return jnp.where(mode == NON_COH, nc, jnp.where(
            mode == LLC_COH, lc, jnp.where(mode == COH_DMA, cd, fc)))

    comm = by_mode(nc_comm, lc_comm, cd_comm, fc_comm)
    off_bytes = by_mode(nc_off, lc_off, cd_off, fc_off)
    compute = cpb * fp * reuse
    active = (jnp.maximum(compute, comm)
              + SERIAL_FRAC * jnp.minimum(compute, comm))
    return ovh + active, comm, active, off_bytes / s.line, my_dram, my_llc


# ------------------------------------------------------------------ step
class Row(NamedTuple):
    """One invocation's inputs (schedule row, policy mode, noise, decay)."""

    acc_id: jnp.ndarray
    footprint: jnp.ndarray
    tiles: jnp.ndarray      # (n_tiles,) bool
    thread: jnp.ndarray
    fresh: jnp.ndarray
    others: jnp.ndarray     # (T,) bool
    valid: jnp.ndarray
    pre_mode: jnp.ndarray
    profile: jnp.ndarray    # (9,)
    avail: jnp.ndarray      # (A,) bool
    eps: jnp.ndarray
    alpha: jnp.ndarray
    u_explore: jnp.ndarray
    g_pick: jnp.ndarray     # (A,)
    g_tie: jnp.ndarray      # (A,)


def slot_table(n_slots: int, n_tiles: int) -> jnp.ndarray:
    """Per-slot state: [mode, footprint, warmth, dram, llc, fp/tile, tiles]."""
    t = jnp.zeros((n_slots, 6 + n_tiles), F32)
    return t.at[:, 0].set(-1.0).at[:, 2].set(1.0)


def _bucket_fp(b, s: Static):
    return jnp.where(b <= s.l2_bytes, 0,
                     jnp.where(b <= s.llc_slice_bytes, 1, 2)).astype(I32)


def sense(o_modes, o_tiles, o_fpt, tiles, fp, s: Static):
    """Paper Table 3's five attributes, encoded as one state index."""
    active = o_modes >= 0
    fully = jnp.sum(jnp.where(active & (o_modes == FULLY_COH), 1, 0))
    n_t = jnp.maximum(jnp.sum(tiles.astype(I32)), 1)
    ti = o_tiles.astype(I32)
    nc = (active & (o_modes == NON_COH)).astype(I32)
    avg_nc = jnp.sum(jnp.where(tiles, jnp.sum(ti * nc[:, None], 0), 0)) / n_t
    lm = (active & (o_modes != NON_COH)).astype(I32)
    avg_llc = jnp.sum(jnp.where(tiles, jnp.sum(ti * lm[:, None], 0), 0)) / n_t
    per_tile_b = jnp.sum(o_tiles.astype(F32) * o_fpt[:, None], 0)
    avg_b = jnp.sum(jnp.where(tiles, per_tile_b, 0.0)) / n_t
    attrs = [jnp.clip(fully.astype(I32), 0, 2),
             jnp.clip(jnp.round(avg_nc).astype(I32), 0, 2),
             jnp.clip(jnp.round(avg_llc).astype(I32), 0, 2),
             _bucket_fp(avg_b, s), _bucket_fp(fp, s)]
    return attrs[0] + attrs[1] * 3 + attrs[2] * 9 + attrs[3] * 27 \
        + attrs[4] * 81


def reward(extrema, acc, exec_t, comm, active, off, fp, w):
    """Paper reward against the accelerator's running extrema (incl. this)."""
    fpc = jnp.maximum(fp, 1.0)
    vals = jnp.stack([exec_t / fpc, comm / jnp.maximum(active, 1.0),
                      off / fpc, off / fpc])
    col = pick(extrema.T, acc)
    is_min = jnp.arange(4) != 3
    new = jnp.where(is_min, jnp.minimum(col, vals), jnp.maximum(col, vals))
    new = jnp.where(jnp.isfinite(new), new, col)
    r_exec = new[0] / jnp.maximum(vals[0], TINY)
    r_comm = new[1] / jnp.maximum(vals[1], TINY)
    span = new[3] - new[2]
    r_mem = jnp.where(span > TINY,
                      1.0 - (vals[2] - new[2]) / jnp.maximum(span, TINY), 1.0)
    r = w[0] * r_exec + w[1] * r_comm + w[2] * r_mem
    return r, put(extrema.T, acc, new).T


def step(s: Static, learned, w, qtable, extrema, tbl, x: Row, rnd=identity):
    """One sense -> select -> time -> reward -> learn invocation.

    Returns ``(qtable, extrema, tbl, y)``, ``y`` = [mode, state, action,
    exec cycles, off-chip lines, reward].  A ``valid=False`` row leaves
    every carried state as it was."""
    n_tiles = tbl.shape[-1] - 6
    omask = x.others & (tbl[:, 0] >= 0.0)
    neutral = jnp.zeros((6 + n_tiles,), F32).at[0].set(-1.0)
    otbl = jnp.where(omask[:, None], tbl, neutral)
    o_modes, o_fps, o_dram, o_llc, o_fpt = (otbl[:, c]
                                            for c in (0, 1, 3, 4, 5))
    o_tiles = otbl[:, 6:]
    state = sense(o_modes, o_tiles, o_fpt, x.tiles, x.footprint, s)
    self_row = pick(tbl, x.thread)
    warm = jnp.where(x.fresh, 1.0, self_row[2])

    row = pick(qtable, state)
    mrow = jnp.where(x.avail, row, NEG)
    is_max = mrow >= jnp.max(mrow) - 1e-9
    greedy = jnp.argmax(jnp.where(is_max & x.avail, 0.0, NEG) + x.g_tie)
    rand = jnp.argmax(jnp.where(x.avail, 0.0, NEG) + x.g_pick)
    choice = jnp.where(x.u_explore < x.eps, rand, greedy).astype(I32)
    q_action = jnp.where(jnp.all(jnp.isfinite(row)), choice, NON_COH)
    action = jnp.where(learned, q_action, x.pre_mode).astype(I32)
    mode = jnp.where(pick(x.avail, action) & jnp.isfinite(x.footprint),
                     action,
                     NON_COH).astype(I32)

    exec_t, comm, active, off, my_dram, my_llc = invocation_timing(
        mode, x.profile, x.footprint, x.tiles, o_modes, o_dram, o_llc,
        o_fps, o_tiles, warm, s)
    r, ext_new = reward(extrema, x.acc_id, exec_t, comm, active, off,
                        x.footprint, w)

    ok = jnp.isfinite(r)
    a = jnp.where(ok, x.alpha, 0.0)
    hot = jnp.arange(row.shape[0]) == action
    new_row = jnp.where(hot, (1.0 - a) * row + a * jnp.where(ok, r, 0.0),
                        row)
    warm_cap = s.llc_slice_bytes * s.n_mem_tiles + s.n_cpus * s.l2_bytes
    warmth = jnp.where(mode == NON_COH, 0.0, jnp.minimum(
        1.0, warm_cap / jnp.maximum(x.footprint, 1.0)))
    new_slot = jnp.concatenate([
        jnp.stack([mode.astype(F32), x.footprint, warmth, my_dram, my_llc,
                   x.footprint / jnp.maximum(jnp.sum(x.tiles), 1)]),
        x.tiles.astype(F32)])
    new_row = jnp.where(x.valid, new_row, row)
    new_slot = jnp.where(x.valid, new_slot, self_row)
    ext_new = jnp.where(x.valid, ext_new, extrema)
    y = jnp.stack([mode.astype(F32), state.astype(F32), action.astype(F32),
                   exec_t, off, r])
    return (rnd(put(qtable, state, new_row)), rnd(ext_new),
            rnd(put(tbl, x.thread, new_slot)), rnd(y))


def init_extrema(n_accs: int) -> jnp.ndarray:
    return jnp.concatenate([jnp.full((3, n_accs), BIG, F32),
                            jnp.zeros((1, n_accs), F32)])


def select_noise(key, n: int):
    k_explore, k_pick, k_tie = jax.random.split(key, 3)
    return (jax.random.uniform(k_explore, (n,)),
            jax.random.gumbel(k_pick, (n, N_MODES)),
            jax.random.gumbel(k_tie, (n, N_MODES)))


# --------------------------------------------------------------- episode
def episode(static, pmat, masks, sched, modes, learned, qtable0, frozen,
            step0, w, key, decay_steps, rnd=identity):
    """One agent over one (padded) schedule: ``(qtable, visits_add,
    n_inc, per-phase time, per-phase off-chip, y (S, 6))``."""
    s = Static(*[static[i] for i in range(len(STATIC_FIELDS))])
    n = sched["acc_id"].shape[0]
    u, g_pick, g_tie = select_noise(key, n)
    inc = (sched["valid"] & ~frozen).astype(I32)
    step_t = step0 + jnp.cumsum(inc) - inc
    frac = jnp.clip(1.0 - step_t.astype(F32) / decay_steps, 0.0, 1.0)
    eps = jnp.where(frozen, 0.0, EPS0 * frac)
    alpha = jnp.where(frozen, 0.0, ALPHA0 * frac)
    acc = sched["acc_id"]
    xs = Row(acc, sched["footprint"], sched["tiles"], sched["thread"],
             sched["fresh"], sched["others"], sched["valid"], modes,
             pmat[acc], masks[acc], eps, alpha, u, g_pick, g_tie)
    n_t, n_tiles = sched["others"].shape[-1], sched["tiles"].shape[-1]

    def body(carry, x):
        q, e, t = carry
        q, e, t, y = step(s, learned, w, q, e, t, x, rnd)
        return (q, e, t), y

    (q, _, _), y = jax.lax.scan(
        body, (qtable0, init_extrema(pmat.shape[0]),
               slot_table(n_t, n_tiles)), xs)
    return q, y, inc


def phase_metrics(sched, y, n_phases: int):
    valid = sched["valid"]
    secs = jnp.where(valid, y[:, 3], 0.0) * CYCLE_TIME
    per = jnp.zeros((n_phases, sched["others"].shape[-1]), F32).at[
        sched["phase_id"], sched["thread"]].add(secs)
    off = jnp.zeros((n_phases,), F32).at[sched["phase_id"]].add(
        jnp.where(valid, y[:, 4], 0.0))
    return jnp.max(per, axis=1), off


def visits_of(y, inc):
    return jnp.zeros((N_STATES, N_MODES), I32).at[
        y[:, 1].astype(I32), y[:, 2].astype(I32)].add(inc)


# -------------------------------------------------------------- training
def _train_agent(static, pmat, masks, scheds, decay_steps, w, key, rnd):
    def body(carry, sched):
        q, visits, step0, key = carry
        key, k_train, _ = jax.random.split(key, 3)
        q, y, inc = episode(static, pmat, masks, sched,
                            jnp.zeros_like(sched["acc_id"]), True, q,
                            jnp.zeros((), bool), step0, w, k_train,
                            decay_steps, rnd)
        return (q, visits + visits_of(y, inc), step0 + jnp.sum(inc),
                key), None

    carry0 = (jnp.full((N_STATES, N_MODES), Q_INIT, F32),
              jnp.zeros((N_STATES, N_MODES), I32), jnp.zeros((), I32), key)
    (q, visits, steps, _), _ = jax.lax.scan(body, carry0, scheds)
    return q, visits, steps


@functools.lru_cache(maxsize=None)
def _train_fn(rnd):
    agents = jax.vmap(_train_agent,
                      in_axes=(None, None, None, None, None, 0, 0, None))
    return jax.jit(jax.vmap(agents, in_axes=(0, 0, 0, 0, 0, None, 0, None)),
                   static_argnums=(7,))


def train(lanes: Lanes, scheds, decay_steps, weights, keys, rnd=identity):
    """(K lanes x B agents) trained from a fresh optimistic table.

    ``scheds`` leaves are (K, iters, S, ...); ``weights`` (B, 3);
    ``keys`` (K, B, 2); ``decay_steps`` (K,).  Returns (qtable, visits,
    step) with (K, B, ...) leaves."""
    return _train_fn(rnd)(
        lanes.static, lanes.pmat, lanes.masks, scheds,
        jnp.asarray(decay_steps, I32), jnp.asarray(weights, F32),
        jnp.asarray(keys), rnd)


# ------------------------------------------------------------ evaluation
def fixed_modes(masks, acc_id, mode: int):
    return jnp.where(masks[acc_id, mode], mode, NON_COH).astype(I32)


def manual_modes(static, masks, sched):
    """Paper Algorithm 1, replayed over the schedule's concurrency."""
    s = Static(*[static[i] for i in range(len(STATIC_FIELDS))])
    n_t = sched["others"].shape[-1]
    llc = s.llc_slice_bytes * s.n_mem_tiles

    def body(carry, x):
        t_mode, t_fp = carry
        acc, fp, others, thread, valid = x
        avail = pick(masks, acc)
        om = others & (t_mode >= 0)
        o_modes = jnp.where(om, t_mode, -1)
        a_fp = jnp.sum(jnp.where(om, t_fp, 0.0))
        act = o_modes >= 0
        n_cd = jnp.sum(act & (o_modes == COH_DMA))
        n_fc = jnp.sum(act & (o_modes == FULLY_COH))
        n_nc = jnp.sum(act & (o_modes == NON_COH))
        m = jnp.where(fp <= EXTRA_SMALL, FULLY_COH, jnp.where(
            fp <= s.l2_bytes, jnp.where(n_cd > n_fc, FULLY_COH, COH_DMA),
            jnp.where(fp + a_fp > llc, NON_COH,
                      jnp.where(n_nc >= 2, LLC_COH, COH_DMA))))
        m = jnp.where(pick(avail, m), m, NON_COH)
        mode = jnp.where(pick(avail, m), m, NON_COH).astype(I32)
        new = (put(t_mode, thread, mode), put(t_fp, thread, fp))
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(valid, a, b), new, carry), mode

    _, modes = jax.lax.scan(
        body, (jnp.full((n_t,), -1, I32), jnp.zeros((n_t,), F32)),
        (sched["acc_id"], sched["footprint"], sched["others"],
         sched["thread"], sched["valid"]))
    return modes


def _eval_lane(static, pmat, masks, sched, qtables, learned, modes_kind,
               keys, n_phases, rnd):
    """Every policy of one lane: rows of ``modes_kind`` are 0..3 (fixed
    mode), 4 (manual) or -1 (table policy from ``qtables``)."""
    manual = manual_modes(static, masks, sched)
    fixed = jnp.stack([fixed_modes(masks, sched["acc_id"], m)
                       for m in range(N_MODES)])

    def one(qt, lrn, kind, key):
        modes = jnp.where(kind == 4, manual,
                          fixed[jnp.clip(kind, 0, 3)]).astype(I32)
        modes = jnp.where(lrn, 0, modes)
        _, y, _ = episode(static, pmat, masks, sched, modes, lrn, qt,
                          jnp.ones((), bool), jnp.zeros((), I32),
                          jnp.asarray([0.675, 0.075, 0.25], F32), key,
                          jnp.ones((), I32), rnd)
        pt, po = phase_metrics(sched, y, n_phases)
        return pt, po, y

    return jax.vmap(one)(qtables, learned, modes_kind, keys)


@functools.lru_cache(maxsize=None)
def _eval_fn(rnd):
    return jax.jit(jax.vmap(_eval_lane, in_axes=(0, 0, 0, 0, 0, None, None,
                                                 0, None, None)),
                   static_argnums=(8, 9))


def evaluate(lanes: Lanes, sched, qtables, learned, modes_kind, keys,
             n_phases: int, rnd=identity):
    """(K lanes x N policies): per-phase time, off-chip and trace rows."""
    return _eval_fn(rnd)(lanes.static, lanes.pmat, lanes.masks, sched, qtables,
              jnp.asarray(learned), jnp.asarray(modes_kind, I32),
              jnp.asarray(keys), n_phases, rnd)


# --------------------------------------------------------------- serving
class Traffic(NamedTuple):
    """An offered stream (MMPP-2, K tenants); leaves are f32 scalars."""

    rate: float
    burst_rate: float
    p_burst: float
    p_calm: float
    mix: tuple
    deadline: tuple
    priority: tuple
    backoff: float
    overload_frac: float
    pressure_beta: float
    prio_reserve: float


def arrivals(tr: Traffic, key, n: int, n_rows: int, t0):
    """One chunk's arrivals: (t_arr, row, tenant, deadline, priority)."""
    k_state, k_gap, k_row, k_ten = jax.random.split(key, 4)
    u_state = jax.random.uniform(k_state, (n,), F32)
    u_gap = jax.random.uniform(k_gap, (n,), F32)
    u_row = jax.random.uniform(k_row, (n,), F32)
    mix = jnp.asarray(tr.mix, F32)
    g_ten = jax.random.gumbel(k_ten, (n, mix.shape[0]), F32)
    p_calm, p_burst = jnp.asarray(tr.p_calm, F32), jnp.asarray(
        tr.p_burst, F32)

    def flip(high, u):
        high = jnp.where(high, u >= p_calm, u < p_burst)
        return high, high

    _, burst = jax.lax.scan(flip, jnp.zeros((), bool), u_state)
    rate = jnp.asarray(tr.rate, F32) * jnp.where(
        burst, jnp.asarray(tr.burst_rate, F32), 1.0)
    gaps = -jnp.log1p(-u_gap * np.float32(1 - 1e-7)) / jnp.maximum(
        rate, np.float32(1e-12))
    t_arr = jnp.asarray(t0, F32) + jnp.cumsum(gaps)
    k = mix.shape[0]
    tenant = jnp.argmax(jnp.log(jnp.maximum(mix, np.float32(1e-12)))[None]
                        + g_ten, axis=-1).astype(I32)
    lo = (tenant * n_rows) // k
    hi = ((tenant + 1) * n_rows) // k
    row = lo + jnp.floor(u_row * jnp.maximum(hi - lo, 1).astype(F32)
                         ).astype(I32)
    row = jnp.clip(row, 0, n_rows - 1)
    dl = jnp.asarray(tr.deadline, F32)[tenant]
    deadline = t_arr + jnp.where(dl <= 0.0, NO_DEADLINE, dl)
    prio = jnp.clip(jnp.asarray(tr.priority, F32)[tenant], 0.0, 1.0)
    return t_arr, row, tenant, deadline, prio


class Queues(NamedTuple):
    """Serving state that crosses chunks."""

    qtable: jnp.ndarray
    extrema: jnp.ndarray
    tbl: jnp.ndarray
    busy: jnp.ndarray
    fin: jnp.ndarray
    head: jnp.ndarray
    pressure: jnp.ndarray
    tripped: jnp.ndarray
    step: jnp.ndarray


def init_queues(qtable, n_accs, n_tiles, queue_cap, step0) -> Queues:
    return Queues(jnp.asarray(qtable, F32), init_extrema(n_accs),
                  slot_table(n_accs, n_tiles), jnp.zeros((n_accs,), F32),
                  jnp.zeros((n_accs, queue_cap), F32),
                  jnp.zeros((n_accs,), I32), jnp.zeros((), F32),
                  jnp.zeros((), F32), jnp.asarray(step0, I32))


def _serve_step(s, learned, w, tr: Traffic, frozen, decay_steps, c: Queues,
                x: Row, t_arr, deadline, prio, rnd):
    acc = x.acc_id
    n_accs, cap = c.fin.shape
    busy_a = pick(c.busy, acc)
    frow = pick(c.fin, acc)
    degraded = c.tripped != 0.0
    live = ~frozen
    cap_eff = np.float32(cap) - tr.prio_reserve * np.float32(cap) * (
        1.0 - prio)
    oks, starts = [], []
    for r in range(MAX_RETRIES + 1):
        t_r = t_arr + tr.backoff * np.float32(2.0 ** r - 1.0)
        depth = jnp.sum((frow > t_r).astype(F32))
        start_r = jnp.maximum(t_r, busy_a)
        oks.append((depth < cap_eff) & (start_r <= deadline))
        starts.append(start_r)
    executed = jnp.any(jnp.stack(oks))
    first = jnp.argmax(jnp.stack(oks))       # first admissible attempt
    start = jnp.where(executed, pick(jnp.stack(starts), first), starts[0])
    retries = jnp.where(executed, first.astype(F32),
                        np.float32(MAX_RETRIES + 1))
    depth0 = jnp.sum((frow > t_arr).astype(F32))
    frac = jnp.clip(1.0 - c.step.astype(F32) / decay_steps, 0.0, 1.0)
    xr = x._replace(
        thread=acc, fresh=jnp.ones((), bool),
        others=(c.busy > start) & (jnp.arange(n_accs) != acc),
        valid=executed, eps=jnp.where(live, EPS0 * frac, 0.0),
        alpha=jnp.where(live, ALPHA0 * frac, 0.0),
        pre_mode=jnp.where(degraded, NON_COH, x.pre_mode).astype(I32))
    qtable, ext, tbl, y = step(s, learned & ~degraded, w, c.qtable,
                               c.extrema, c.tbl, xr, rnd)
    ex = executed.astype(F32)
    finish = start + y[3]
    head_a = pick(c.head, acc)
    fin = put(c.fin, acc, jnp.where(
        (jnp.arange(cap) == head_a) & executed, finish, frow))
    hot = (jnp.arange(n_accs) == acc) & executed
    nxt = head_a + 1
    head = jnp.where(hot, jnp.where(nxt >= cap, 0, nxt), c.head)
    busy = jnp.where(hot, finish, c.busy)
    pressure = ((1.0 - tr.pressure_beta) * c.pressure
                + tr.pressure_beta * (1.0 - ex))
    over = (tr.overload_frac > 0.0) & (pressure > tr.overload_frac)
    rising = over & (c.tripped == 0.0)
    reopened = jnp.minimum(c.step, (decay_steps * (1.0 - REOPEN_FRAC)
                                    ).astype(I32))
    stp = jnp.where(rising & live, reopened, c.step)
    stp = stp + jnp.where(executed & live, 1, 0).astype(I32)
    tripped = jnp.where(over, 1.0, jnp.where(
        pressure >= 0.5 * tr.overload_frac, c.tripped, 0.0))
    out = jnp.stack([
        jnp.where(executed, y[0], -1.0), jnp.where(executed, y[1], -1.0),
        jnp.where(executed, y[2], -1.0), y[3] * ex, y[4] * ex, y[5] * ex,
        ex, (finish - t_arr) * ex, retries, depth0, degraded.astype(F32),
        start * ex, finish * ex])
    c = Queues(qtable, ext, tbl, rnd(busy), rnd(fin), head, pressure,
               tripped, stp)
    return c, out


SERVE_COLS = ("mode", "state_idx", "action", "exec_time", "offchip",
              "reward", "executed", "latency", "retries", "depth",
              "degraded", "start", "finish")


def _serve_chunk(static, pmat, masks, sched, learned, frozen, decay_steps,
                 tr, queues, key, tr_key, t0, n, rnd):
    s = Static(*[static[i] for i in range(len(STATIC_FIELDS))])
    n_rows = sched["acc_id"].shape[0]
    t_arr, row, tenant, deadline, prio = arrivals(tr, tr_key, n, n_rows, t0)
    acc = sched["acc_id"][row]
    u, g_pick, g_tie = select_noise(key, n)
    z = jnp.zeros((n,), F32)
    xs = Row(acc, sched["footprint"][row], sched["tiles"][row],
             jnp.zeros((n,), I32), jnp.ones((n,), bool),
             jnp.zeros((n, pmat.shape[0]), bool), jnp.ones((n,), bool),
             jnp.zeros((n,), I32), pmat[acc], masks[acc], z, z, u, g_pick,
             g_tie)
    w = jnp.asarray([0.675, 0.075, 0.25], F32)

    def body(c, xv):
        x, ta, dl, pr = xv
        return _serve_step(s, learned, w, tr, frozen, decay_steps, c, x, ta,
                           dl, pr, rnd)

    queues, out = jax.lax.scan(body, queues, (xs, t_arr, deadline, prio))
    return queues, out, t_arr, tenant


@functools.lru_cache(maxsize=None)
def serve_chunk_fn(n: int, rnd=identity):
    """Jitted chunk ``(static, pmat, masks, sched, learned, frozen,
    decay_steps, traffic, queues, key, traffic_key, t0) -> (queues,
    out (n, 13), t_arr, tenant)``; ``traffic`` leaves must be f32."""
    def fn(static, pmat, masks, sched, learned, frozen, decay_steps, tr,
           queues, key, tr_key, t0):
        return _serve_chunk(static, pmat, masks, sched, learned, frozen,
                            decay_steps, tr, queues, key, tr_key, t0, n, rnd)
    return jax.jit(fn)
