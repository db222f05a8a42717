"""Fused SoC episode step — pure-jnp reference semantics.

One step of the vectorized Cohmeleon environment
(:mod:`repro.soc.vecenv`), reformulated so the whole
sense -> select -> time -> reward -> learn cycle is a single pass over
the packed ``(T, 6 + n_tiles)`` slot table and ONE Q-table row:

  * the Q-row for the sensed state is gathered once and shared between
    epsilon-greedy selection and the blend/write-back update (the unfused
    step gathers it twice);
  * the (epsilon, alpha) decay schedule and the step-counter increments
    are precomputed per step *outside* the scan
    (:func:`repro.core.qlearn.decay_arrays`), so the carry holds only the
    Q-table — visits/step diagnostics are reconstructed from the episode
    trace afterwards (:func:`repro.core.qlearn.replay_visits`);
  * each slot's normalized footprint-per-tile (``fp / |tiles|``) is cached
    in the slot table next to the (dram, llc) demand cache and invalidated
    only on slot writes, feeding both the Table-3 sense reductions and the
    per-tile DDR attribution without per-step divisions;
  * everything per-slot lives in ONE ``(T, 6 + n_tiles)`` float32 table
    (:data:`TBL_MODE` .. tile columns), so the per-step bookkeeping is a
    single masked read and a single row write-back instead of seven
    scatter/gather pairs — and the per-step inputs are packed into one
    float row + one int row (:func:`pack_inputs`), so the scan slices two
    arrays per step instead of fifteen.

Every reformulation is value-preserving and almost all are bitwise: the
shared row feeds identical floats to both consumers, integer visit counts
commute, the tile masks are exact {0, 1} factors whether stored as bool
or float32, and the slot-mode column compares identically as float (modes
are small exact integers).  The fused-vs-unfused equivalence tests pin
bitwise equality on CPU.

:func:`episode_ref` scans :func:`fused_step` over a whole episode — it is
both the oracle ``tests/test_kernels.py`` checks the Pallas kernel
against and the fast XLA lowering :mod:`repro.kernels.soc_step.ops`
dispatches to on CPU backends and, on every backend, for calls that
batch many episodes.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qlearn, rewards, state as cstate
from repro.core.modes import CoherenceMode
from repro.core.state import CacheGeometry
from repro.core.vops import iota, put_row, take, take_row
from repro.soc import nn as socnn
from repro.soc.faults import StepFault
from repro.soc.memsys import SoCStatic, invocation_perf_cached, warmth_after

# Packed slot-table column layout: one (T, N_TBL_COLS + n_tiles) float32
# array is the whole per-thread carry (mode compares exactly as float;
# tile columns are {0, 1} factors, which every consumer casts or
# multiplies — bitwise-identical to the unfused bool/int arrays).
TBL_MODE, TBL_FP, TBL_WARM, TBL_DRAM, TBL_LLC, TBL_FPT = range(6)
N_TBL_COLS = 6

# Column order of the packed per-step trace row (int columns are exact
# small integers in f32; unpack_ys restores their dtypes).
YCOLS = ("mode", "state_idx", "action", "exec_time", "offchip", "reward")

# Column order of the packed int input row (see pack_inputs).
ICOLS = ("acc_id", "thread", "fresh", "valid", "pre_mode")


def tbl_width(n_tiles: int) -> int:
    return N_TBL_COLS + n_tiles


def init_slot_table(n_threads: int, n_tiles: int) -> jnp.ndarray:
    """Fresh packed slot table: mode=-1 (never used), warmth=1, rest 0."""
    col = jax.lax.broadcasted_iota(jnp.int32, (n_threads, tbl_width(n_tiles)),
                                   1)
    return jnp.where(col == TBL_MODE, np.float32(-1.0),
                     jnp.where(col == TBL_WARM, np.float32(1.0),
                               np.float32(0.0)))


def _neutral_row(n_tiles: int) -> jnp.ndarray:
    """What an inactive slot reads as: mode=-1, every contribution 0."""
    return jnp.where(iota(tbl_width(n_tiles)) == TBL_MODE, np.float32(-1.0),
                     np.float32(0.0))


class StepInputs(NamedTuple):
    """Per-step xs of the fused episode.

    A schedule row, the lowered policy's precomputed mode, the pregathered
    per-accelerator rows (``pmat[acc_id]`` / ``masks[acc_id]`` — hoisting
    the gathers out of the scan is value-identical), the precomputed decay
    schedule and the pre-sampled select noise.  Leaves carry a leading
    (S,) axis when fed to :func:`episode_ref` / :func:`pack_inputs`."""

    acc_id: jnp.ndarray      # () int32
    footprint: jnp.ndarray   # () float32 bytes
    tiles: jnp.ndarray       # (n_tiles,) bool
    thread: jnp.ndarray      # () int32
    fresh: jnp.ndarray       # () bool
    others: jnp.ndarray      # (T,) bool
    valid: jnp.ndarray       # () bool
    pre_mode: jnp.ndarray    # () int32 — the PolicySpec mode table row
    profile: jnp.ndarray     # (F,) float32 — pmat[acc_id]
    avail: jnp.ndarray       # (A,) bool — masks[acc_id]
    eps: jnp.ndarray         # () float32 precomputed epsilon
    alpha: jnp.ndarray       # () float32 precomputed alpha
    u_explore: jnp.ndarray   # () float32
    g_pick: jnp.ndarray      # (A,) float32 gumbel
    g_tie: jnp.ndarray       # (A,) float32 gumbel
    # Optional pre-sampled fault rows (repro.soc.faults.StepFault columns).
    # None (the default) keeps the healthy program: None fields are empty
    # pytree nodes, so they scan/pack away to nothing at trace time.
    f_exec: jnp.ndarray | None = None   # () float32 compute-cost multiplier
    f_ddr: jnp.ndarray | None = None    # () float32 dram_bw multiplier
    f_llc: jnp.ndarray | None = None    # () float32 extra LLC load
    f_retry: jnp.ndarray | None = None  # () float32 retry backoff cycles


def pack_inputs(xs: StepInputs) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pack an (S,)-leading :class:`StepInputs` into ``(xf, xi)``.

    ``xf`` is ``(S, 4 + n_tiles + T + F + 3A [+ 4])`` float32 —
    ``[footprint, eps, alpha, u_explore, tiles, others, profile, avail,
    g_pick, g_tie]`` plus, when the episode is fault-injected, the four
    :class:`~repro.soc.faults.StepFault` columns — and ``xi`` is ``(S,
    5)`` int32 (:data:`ICOLS`).  This is the Pallas kernel's input
    layout: one float row + one int row per grid step instead of fifteen
    blocked operands; boolean masks ride as exact {0, 1} floats.  (The
    XLA ``lax.scan`` lowering feeds the leaves directly — per-step row
    unpacking costs more than it saves there.)"""
    f32, i32 = jnp.float32, jnp.int32
    cols = [
        jnp.stack([xs.footprint.astype(f32), xs.eps.astype(f32),
                   xs.alpha.astype(f32), xs.u_explore.astype(f32)],
                  axis=-1),
        xs.tiles.astype(f32), xs.others.astype(f32),
        xs.profile.astype(f32), xs.avail.astype(f32),
        xs.g_pick.astype(f32), xs.g_tie.astype(f32)]
    if xs.f_exec is not None:
        cols.append(jnp.stack([xs.f_exec.astype(f32), xs.f_ddr.astype(f32),
                               xs.f_llc.astype(f32),
                               xs.f_retry.astype(f32)], axis=-1))
    xf = jnp.concatenate(cols, axis=-1)
    xi = jnp.stack([xs.acc_id.astype(i32), xs.thread.astype(i32),
                    xs.fresh.astype(i32), xs.valid.astype(i32),
                    xs.pre_mode.astype(i32)], axis=-1)
    return xf, xi


def unpack_inputs(xf: jnp.ndarray, xi: jnp.ndarray, *, n_tiles: int,
                  n_threads: int, n_actions: int,
                  faulted: bool = False) -> StepInputs:
    """Invert :func:`pack_inputs` for ONE step row (no leading axis).

    Static slices of the packed rows fuse into their consumers; bool
    fields are restored with exact ``!= 0`` compares.  ``faulted`` (a
    static flag, mirroring whether ``pack_inputs`` saw fault columns)
    recovers the trailing :class:`~repro.soc.faults.StepFault` columns."""
    o = 4
    tiles = xf[o:o + n_tiles] != 0.0
    o += n_tiles
    others = xf[o:o + n_threads] != 0.0
    o += n_threads
    n_feat = xf.shape[-1] - o - 3 * n_actions - (4 if faulted else 0)
    profile = xf[o:o + n_feat]
    o += n_feat
    avail = xf[o:o + n_actions] != 0.0
    o += n_actions
    g_pick = xf[o:o + n_actions]
    o += n_actions
    g_tie = xf[o:o + n_actions]
    o += n_actions
    fault = {}
    if faulted:
        fault = dict(f_exec=xf[o], f_ddr=xf[o + 1], f_llc=xf[o + 2],
                     f_retry=xf[o + 3])
    return StepInputs(
        acc_id=xi[0], thread=xi[1], fresh=xi[2] != 0, valid=xi[3] != 0,
        pre_mode=xi[4], footprint=xf[0], eps=xf[1], alpha=xf[2],
        u_explore=xf[3], tiles=tiles, others=others, profile=profile,
        avail=avail, g_pick=g_pick, g_tie=g_tie, **fault)


def unpack_ys(y: jnp.ndarray) -> tuple:
    """Split the stacked ``(S, 6)`` trace (:data:`YCOLS`) back into typed
    per-step arrays."""
    i32 = jnp.int32
    return (y[:, 0].astype(i32), y[:, 1].astype(i32), y[:, 2].astype(i32),
            y[:, 3], y[:, 4], y[:, 5])


def fused_step(s: SoCStatic, geom: CacheGeometry, warm_cap, learned,
               weights, qtable, rs, tbl, x: StepInputs, *,
               ddr_attribution: bool = False, gated: bool = False,
               wpack=None, qfun=None, mlp_lr=None, mlp_dims=None,
               mlp_feats: str = "sense", slack=None, reuse=None):
    """One fused sense->select->time->reward->learn step.

    Pure values in, pure values out — the Pallas kernel body loads its
    scratch, calls this, and stores the results, so kernel and reference
    cannot drift.  ``tbl`` is the packed ``(T, 6 + n_tiles)`` slot table;
    returns ``(qtable, rs, tbl, y)`` with ``y`` the stacked ``(6,)``
    :data:`YCOLS` trace row.

    ``wpack=None`` (the default) is the exact tabular program.  With a
    packed MLP (:mod:`repro.soc.nn`) the step additionally runs the
    network forward over the sense features and its semi-gradient TD
    update, returning ``(qtable, rs, tbl, wpack, y)``; the traced
    ``qfun`` flag selects which Q-row (table or network) drives
    selection and which agent learns, so mixed table/MLP spec batches
    share one program.  ``slack``/``reuse`` are the serving path's
    HyDRA-style features (episodes default them to 0).
    """
    n_tiles = tbl.shape[-1] - N_TBL_COLS
    omask = x.others & (tbl[:, TBL_MODE] >= 0.0)
    # ONE masked read serves sense, timing and DDR attribution: inactive
    # slots become the neutral row (mode -1, zero contributions).
    # (The mask goes to a column as int32: the TPU kernel cannot reshape
    # a bool vector.)
    otbl = jnp.where(omask.astype(jnp.int32)[:, None] != 0, tbl,
                     _neutral_row(n_tiles))
    omodes = otbl[:, TBL_MODE]
    ofps = otbl[:, TBL_FP]
    odram = otbl[:, TBL_DRAM]
    ollc = otbl[:, TBL_LLC]
    ofpt = otbl[:, TBL_FPT]
    otiles = otbl[:, N_TBL_COLS:]
    state_idx = cstate.observe(
        active_modes=omodes, active_footprints=ofps, needed_tiles=otiles,
        target_tiles=x.tiles, target_footprint=x.footprint, geom=geom,
        active_fp_per_tile=ofpt)

    self_row = take_row(tbl, x.thread)
    warm_t = jnp.where(x.fresh, 1.0, self_row[TBL_WARM])

    # One shared Q-row gather: selection and update read identical floats.
    row = take_row(qtable, state_idx)
    if wpack is None:
        row_sel = row
        learned_eff = learned
    else:
        # Function-approximation branch (repro.soc.nn): for qfun specs
        # the network's Q-row replaces the table row.  Routing it through
        # the SAME row_select_presampled keeps PR-7's non-finite-row ->
        # NON_COH degradation fallback for free: fault-poisoned weights
        # produce a non-finite row and the step serves non-coherently.
        feats = socnn.step_features(
            mlp_feats, s, state_idx, footprint=x.footprint, tiles=x.tiles,
            omask=omask, omodes=omodes, ofps=ofps, odram=odram,
            warm_t=warm_t, profile=x.profile,
            slack=jnp.float32(0.0) if slack is None else slack,
            reuse=jnp.float32(0.0) if reuse is None else reuse)
        row_mlp = socnn.forward_packed(wpack, feats, mlp_dims)
        row_sel = jnp.where(qfun, row_mlp, row)
        learned_eff = learned | qfun
    q_action = qlearn.row_select_presampled(
        row_sel, x.eps, qlearn.SelectNoise(
            u_explore=x.u_explore, g_pick=x.g_pick, g_tie=x.g_tie),
        x.avail)
    action = jax.lax.select(learned_eff, q_action, x.pre_mode)

    # Degradation safety: a non-finite sense feature (a fault-corrupted
    # footprint) forces the always-available non-coherent mode, like an
    # unavailable action.  ``& True`` on the healthy path is bitwise-free.
    mode = jnp.where(take(x.avail, action) & jnp.isfinite(x.footprint),
                     action,
                     int(CoherenceMode.NON_COH_DMA)).astype(jnp.int32)
    fault = None
    if x.f_exec is not None:
        fault = StepFault(exec_scale=x.f_exec, ddr_scale=x.f_ddr,
                          llc_extra=x.f_llc, retry_cycles=x.f_retry)
    m, aux = invocation_perf_cached(
        mode, x.profile, x.footprint, x.tiles, omodes, odram, ollc,
        ofps, otiles, warm_t, s, fault=fault)
    off_reward = m.offchip_accesses
    if ddr_attribution:
        # Prorated per-tile DDR attribution (paper §4.1(4)); the cached
        # fpt replaces the per-step ``ofps / o_nt`` division.
        myt = x.tiles.astype(jnp.float32)
        n_my = jnp.maximum(jnp.sum(myt), 1.0)
        o_nt = jnp.maximum(jnp.sum(otiles, -1), 1.0)
        my_fp_t = (x.footprint / n_my) * myt
        o_fp_t = jnp.sum(ofpt[:, None] * otiles, 0)
        share = my_fp_t / jnp.maximum(my_fp_t + o_fp_t, 1e-9)
        my_bpt = (m.offchip_accesses * s.line / n_my) * myt
        o_bpt = jnp.sum(((odram * m.exec_time) / o_nt)[:, None] * otiles, 0)
        off_reward = jnp.sum(share * (my_bpt + o_bpt)) / s.line
    meas = rewards.Measurement(
        exec_time=m.exec_time, comm_cycles=m.comm_cycles,
        total_cycles=m.total_cycles, offchip_accesses=off_reward,
        footprint=x.footprint)
    r, rs_new, _ = rewards.evaluate(rs, x.acc_id, meas, weights)

    new_qrow = qlearn.row_update(row, x.alpha, action, r)
    if wpack is not None:
        # qfun specs leave the (placeholder) table bitwise untouched —
        # x.alpha follows the MLP's decay schedule there, so the blend
        # must be overridden, not merely zero-alpha'd.
        new_qrow = jnp.where(qfun, row, new_qrow)
        upd_gate = (qfun & x.valid) if gated else qfun
        wpack_new = socnn.td_update_packed(
            wpack, feats, action, r, x.alpha * mlp_lr, mlp_dims, upd_gate)
    new_slot = jnp.concatenate([
        jnp.stack([mode.astype(jnp.float32), x.footprint,
                   warmth_after(mode, x.footprint, warm_cap),
                   aux["demand_dram"], aux["demand_llc"],
                   x.footprint / jnp.maximum(jnp.sum(x.tiles), 1)]),
        x.tiles.astype(jnp.float32)])
    if gated:
        # Row-level gating is bitwise-equal to the unfused full-pytree
        # where(valid): only the written rows differ between new and old.
        new_qrow = jnp.where(x.valid, new_qrow, row)
        new_slot = jnp.where(x.valid, new_slot, self_row)
        rs_new = jax.tree_util.tree_map(
            lambda n, o: jnp.where(x.valid, n, o), rs_new, rs)
    qtable_new = put_row(qtable, state_idx, new_qrow)
    tbl_new = put_row(tbl, x.thread, new_slot)

    y = jnp.stack([mode.astype(jnp.float32), state_idx.astype(jnp.float32),
                   action.astype(jnp.float32), m.exec_time,
                   m.offchip_accesses, r])
    if wpack is not None:
        return qtable_new, rs_new, tbl_new, wpack_new, y
    return qtable_new, rs_new, tbl_new, y


# --------------------------------------------------------------------------
# Serving mode: the same fused step driven by an open-ended arrival stream
# (repro.soc.traffic) instead of a fixed schedule.  One scan step == one
# OFFERED request in arrival order; the carry additionally holds the
# per-accelerator admission state (bounded finish-time ring buffers), the
# overload-pressure EMA and the in-carry decay counter (the overload
# watchdog may rewind it mid-stream, so it cannot be precomputed outside
# the scan the way ``qlearn.decay_arrays`` does for episodes).
# --------------------------------------------------------------------------

# Per-request serving trace columns, appended after YCOLS.  ``executed``
# gates every other column (a shed request contributes zeros); ``retries``
# is the admitted attempt index (0 = admitted on arrival) or
# FAULT_MAX_RETRIES + 1 when every backoff attempt was shed; ``depth`` is
# the victim accelerator's queue depth at arrival (pre-admission).
SERVE_YCOLS = YCOLS + ("executed", "latency", "retries", "depth",
                       "degraded", "start", "finish")

# Retry budget shared with the fault model (soc.faults.FAULT_MAX_RETRIES;
# a literal here so this module stays import-light for the kernel).
_SERVE_MAX_RETRIES = 3
_SHED_RETRIES = np.float32(_SERVE_MAX_RETRIES + 1)


class ServeParams(NamedTuple):
    """Scalar serving knobs threaded into every serve step (all traced —
    sweeping any of them reuses the compiled program).

    The decay schedule scalars live here rather than precomputing
    ``(eps_t, alpha_t)`` arrays because the overload watchdog rewinds the
    in-carry step counter mid-stream — the schedule must be evaluated
    against the carried counter, with the same float formula as
    :func:`repro.core.qlearn.schedule`."""

    eps0: jnp.ndarray           # () f32 — cfg.epsilon0
    alpha0: jnp.ndarray         # () f32 — cfg.alpha0
    decay_steps: jnp.ndarray    # () f32 — cfg.decay_steps
    reopen_frac: jnp.ndarray    # () f32 — cfg.reopen_frac (overload rewind)
    frozen: jnp.ndarray         # () f32 {0,1} — qstate.frozen
    backoff: jnp.ndarray        # () f32 — admission retry backoff cycles
    overload_frac: jnp.ndarray  # () f32 — shed-EMA trip level (0 disables)
    pressure_beta: jnp.ndarray  # () f32 — shed-EMA coefficient
    prio_reserve: jnp.ndarray   # () f32 — queue fraction reserved by prio


class ServeCarry(NamedTuple):
    """The long-lived serving state (crosses scan chunks and checkpoints).

    ``fin`` is the per-accelerator ring of admitted-request finish times
    (static ``queue_cap`` slots; the queue depth at time t is the count of
    entries > t — exact because admission itself bounds the number
    outstanding), ``busy`` the finish time of the last admitted request
    (devices serve FIFO, so it is the earliest feasible start), ``head``
    the ring write cursor.  ``pressure`` is the shed-rate EMA the overload
    watchdog trips on; ``tripped`` ({0,1} f32) its hysteresis latch;
    ``step`` the in-carry decay counter (see :class:`ServeParams`)."""

    qtable: jnp.ndarray    # (S, A) f32
    extrema: jnp.ndarray   # (4, n_accs) f32 reward extrema
    tbl: jnp.ndarray       # (n_accs, 6 + n_tiles) f32 slot table
    busy: jnp.ndarray      # (n_accs,) f32
    fin: jnp.ndarray       # (n_accs, queue_cap) f32
    head: jnp.ndarray      # (n_accs,) i32
    pressure: jnp.ndarray  # () f32
    tripped: jnp.ndarray   # () f32 {0,1}
    step: jnp.ndarray      # () i32
    # Packed MLP weights when an nn-policy spec is serving (None — an
    # empty pytree slot — for tabular serving, so every existing carry,
    # checkpoint and cross-chunk round trip is structurally unchanged).
    wpack: jnp.ndarray | None = None


def init_serve_carry(qtable0, extrema0, n_accs: int, n_tiles: int,
                     queue_cap: int, step0, wpack0=None) -> ServeCarry:
    """A fresh serving state: idle devices, empty rings, no pressure.

    One slot per accelerator (serving concurrency is between accelerators,
    not application threads), so the slot table has ``n_accs`` rows."""
    return ServeCarry(
        qtable=jnp.asarray(qtable0, jnp.float32),
        extrema=jnp.asarray(extrema0, jnp.float32),
        tbl=init_slot_table(n_accs, n_tiles),
        busy=jnp.zeros((n_accs,), jnp.float32),
        fin=jnp.zeros((n_accs, queue_cap), jnp.float32),
        head=jnp.zeros((n_accs,), jnp.int32),
        pressure=jnp.zeros((), jnp.float32),
        tripped=jnp.zeros((), jnp.float32),
        step=jnp.asarray(step0, jnp.int32),
        wpack=wpack0,
    )


def _backoff_cycles(backoff, retries: int):
    # soc.faults.backoff_cycles with a static retry count (np scalar so it
    # inlines as a literal under Pallas tracing); exp2 of a small integer
    # is exact, retries == 0 contributes exactly +0.0.
    return backoff * np.float32(2.0 ** retries - 1.0)


def serve_step(s: SoCStatic, geom: CacheGeometry, warm_cap, learned,
               weights, sp: ServeParams, carry: ServeCarry, x: StepInputs,
               t_arr, deadline, priority, *,
               ddr_attribution: bool = False, qfun=None, mlp_lr=None,
               mlp_dims=None, mlp_feats: str = "sense"):
    """One offered request: admit-or-shed, then the fused episode step.

    Admission tries ``_SERVE_MAX_RETRIES + 1`` statically-unrolled
    candidates (arrival, then exponentially backed-off retries — the
    PR-7 retry math, :func:`repro.soc.faults.backoff_cycles`); a
    candidate is admissible when the victim accelerator's queue depth at
    that time is under its (priority-weighted) capacity AND the request
    would start before its deadline.  Shed requests leave every carried
    state untouched (the fused step is row-gated on ``executed``).
    Retried requests keep their arrival-order scan slot — an admitted
    retry executes at its backed-off start time, but later arrivals in
    the stream are still processed after it (a documented approximation;
    exact for the zero-retry fast path).

    Sustained shedding raises the ``pressure`` EMA; crossing
    ``overload_frac`` forces NON_COH fallback (graceful degradation: the
    cheapest, always-available mode under overload) and — on the rising
    edge — rewinds the decay counter to the epsilon-reopen point
    (:func:`repro.core.qlearn.reopen_step` arithmetic), so a long-lived
    agent re-explores once the regime shifts instead of serving a stale
    table.  The latch clears at half the trip level (hysteresis).

    ``x`` is a :class:`StepInputs` row whose ``thread``/``fresh``/
    ``others``/``valid``/``eps``/``alpha`` fields are placeholders — the
    serving loop owns those (slot = accelerator, every request fresh,
    concurrency sensed from ``busy``, validity = admitted, schedule from
    the carried counter).  Returns ``(carry, y)`` with ``y`` the stacked
    ``(len(SERVE_YCOLS),)`` trace row.
    """
    f32 = jnp.float32
    acc = x.acc_id
    n_accs = carry.busy.shape[0]
    queue_cap = carry.fin.shape[-1]
    busy_a = take(carry.busy, acc)
    frow = take_row(carry.fin, acc)
    degraded = carry.tripped != 0.0
    live = sp.frozen == 0.0

    # ---- admission control with bounded retry-with-backoff ------------
    cap_eff = (np.float32(queue_cap)
               - sp.prio_reserve * np.float32(queue_cap) * (1.0 - priority))
    oks, starts = [], []
    for r in range(_SERVE_MAX_RETRIES + 1):
        t_r = t_arr + _backoff_cycles(sp.backoff, r)
        depth_r = jnp.sum((frow > t_r).astype(f32))
        start_r = jnp.maximum(t_r, busy_a)
        oks.append((depth_r < cap_eff) & (start_r <= deadline))
        starts.append(start_r)
    # The first admissible attempt (attempt 0 when none is), as scalar
    # selects: the Pallas TPU kernel cannot stack bools or argmax them.
    executed = jnp.zeros((), bool)
    attempt, start = jnp.zeros((), jnp.int32), starts[0]
    for r in reversed(range(_SERVE_MAX_RETRIES + 1)):
        executed = executed | oks[r]
        attempt = jnp.where(oks[r], r, attempt)
        start = jnp.where(oks[r], starts[r], start)
    retries = jnp.where(executed, attempt.astype(f32), _SHED_RETRIES)
    depth0 = jnp.sum((frow > t_arr).astype(f32))

    # ---- decay schedule from the carried counter (qlearn.schedule) ----
    frac = jnp.clip(1.0 - carry.step.astype(f32) / sp.decay_steps,
                    0.0, 1.0)
    eps = jnp.where(live, sp.eps0 * frac, 0.0)
    alpha = jnp.where(live, sp.alpha0 * frac, 0.0)

    # ---- the fused sense->select->time->reward->learn step ------------
    # Forced NON_COH under overload: learned routes through the pre_mode
    # branch, and the Q update stays on-policy (the observed action IS
    # NON_COH while degraded).
    others = (carry.busy > start) & (iota(n_accs) != acc)
    si = x._replace(
        thread=acc, fresh=jnp.ones((), bool), others=others,
        valid=executed, eps=eps, alpha=alpha,
        pre_mode=jnp.where(degraded, int(CoherenceMode.NON_COH_DMA),
                           x.pre_mode).astype(jnp.int32))
    wpack_new = None
    if carry.wpack is None:
        qtable, rs, tbl, y = fused_step(
            s, geom, warm_cap, learned & ~degraded, weights, carry.qtable,
            rewards.RewardState(extrema=carry.extrema), carry.tbl, si,
            ddr_attribution=ddr_attribution, gated=True)
    else:
        # nn-policy serving: overload degradation gates the network
        # exactly like the table (qfun & ~degraded routes through the
        # forced-NON_COH pre_mode), and the HyDRA-style features are live
        # here — slack is time-to-deadline at arrival, reuse the idle gap
        # since this accelerator's last admitted work.
        qtable, rs, tbl, wpack_new, y = fused_step(
            s, geom, warm_cap, learned & ~degraded, weights, carry.qtable,
            rewards.RewardState(extrema=carry.extrema), carry.tbl, si,
            ddr_attribution=ddr_attribution, gated=True,
            wpack=carry.wpack, qfun=qfun & ~degraded, mlp_lr=mlp_lr,
            mlp_dims=mlp_dims, mlp_feats=mlp_feats,
            slack=deadline - t_arr, reuse=t_arr - busy_a)

    # ---- queue/ring bookkeeping ---------------------------------------
    ex_f = executed.astype(f32)
    exec_time = y[3]
    finish = start + exec_time
    head_a = take(carry.head, acc)
    slot_hot = (iota(queue_cap) == head_a) & executed
    fin = put_row(carry.fin, acc, jnp.where(slot_hot, finish, frow))
    nxt = head_a + 1
    acc_hot = iota(n_accs) == acc
    head = jnp.where(acc_hot & executed, jnp.where(nxt >= queue_cap, 0, nxt),
                     carry.head)
    busy = jnp.where(acc_hot & executed, finish, carry.busy)

    # ---- overload watchdog --------------------------------------------
    pressure = ((1.0 - sp.pressure_beta) * carry.pressure
                + sp.pressure_beta * (1.0 - ex_f))
    wd_on = sp.overload_frac > 0.0
    over = wd_on & (pressure > sp.overload_frac)
    rising = over & (carry.tripped == 0.0)
    reopened = jnp.minimum(
        carry.step,
        (sp.decay_steps * (1.0 - sp.reopen_frac)).astype(jnp.int32))
    step = jnp.where(rising & live, reopened, carry.step)
    step = step + jnp.where(executed & live, 1, 0).astype(jnp.int32)
    tripped = jnp.where(
        over, 1.0,
        jnp.where(pressure >= 0.5 * sp.overload_frac, carry.tripped, 0.0))

    y_serve = jnp.stack([
        jnp.where(executed, y[0], -1.0),          # mode
        jnp.where(executed, y[1], -1.0),          # state_idx
        jnp.where(executed, y[2], -1.0),          # action
        y[3] * ex_f,                              # exec_time
        y[4] * ex_f,                              # offchip
        y[5] * ex_f,                              # reward
        ex_f,                                     # executed
        (finish - t_arr) * ex_f,                  # latency
        retries,                                  # retries (shed = R + 1)
        depth0,                                   # queue depth at arrival
        degraded.astype(f32),                     # degraded this step
        start * ex_f,                             # admitted start time
        finish * ex_f,                            # admitted finish time
    ])
    new_carry = ServeCarry(
        qtable=qtable, extrema=rs.extrema, tbl=tbl, busy=busy, fin=fin,
        head=head, pressure=pressure, tripped=tripped, step=step,
        wpack=wpack_new)
    return new_carry, y_serve


def serve_episode_ref(s: SoCStatic, learned, weights, sp: ServeParams,
                      carry0: ServeCarry, xs: StepInputs, t_arr, deadline,
                      priority, *, ddr_attribution: bool = False,
                      qfun=None, mlp_lr=None, mlp_dims=None,
                      mlp_feats: str = "sense"):
    """Scan :func:`serve_step` over an arrival-stream chunk (pure XLA).

    ``xs`` leaves and the three serving columns carry a leading
    (n_requests,) axis.  Returns ``(carry_final, ys (n_requests,
    len(SERVE_YCOLS)))`` — the carry round-trips into the next chunk (and
    through checkpoints) unchanged.  A carry holding packed MLP weights
    (``carry0.wpack``) serves the nn policy; the weights ride the carry.
    """
    geom, warm_cap = derive_geom(s)

    def step(carry, xv):
        x, t_a, dl, pr = xv
        return serve_step(s, geom, warm_cap, learned, weights, sp, carry,
                          x, t_a, dl, pr, ddr_attribution=ddr_attribution,
                          qfun=qfun, mlp_lr=mlp_lr, mlp_dims=mlp_dims,
                          mlp_feats=mlp_feats)

    return jax.lax.scan(step, carry0, (xs, t_arr, deadline, priority))


def derive_geom(s: SoCStatic) -> tuple[CacheGeometry, jnp.ndarray]:
    """(cache geometry, warmth capacity) from the static scalar bundle."""
    geom = CacheGeometry(l2_bytes=s.l2_bytes,
                         llc_slice_bytes=s.llc_slice_bytes,
                         n_mem_tiles=s.n_mem_tiles)
    warm_cap = s.llc_slice_bytes * s.n_mem_tiles + s.n_cpus * s.l2_bytes
    return geom, warm_cap


@jax.custom_batching.custom_vmap
def _per_episode(carry, ref):
    """``carry`` itself.  Under ``vmap`` over episodes (``ref`` batched) its
    unbatched leaves are broadcast over the batch, so a scan entered with
    it has its carry batched from the start: the scan's batching rule then
    batches the step body once, where a constant initial slot table or
    reward extrema would make it batch the body a second time at its
    fixpoint."""
    return carry


@_per_episode.def_vmap
def _per_episode_vmap(axis_size, in_batched, carry, ref):
    carry_batched, ref_batched = in_batched
    if not any(jax.tree_util.tree_leaves(ref_batched)):
        return _per_episode(carry, ref), carry_batched
    carry = jax.tree_util.tree_map(
        lambda x, b: x if b else jnp.broadcast_to(x, (axis_size,) + x.shape),
        carry, carry_batched)
    return (_per_episode(carry, ref),
            jax.tree_util.tree_map(lambda _: True, carry_batched))


def episode_ref(s: SoCStatic, learned, weights, qtable0, extrema0,
                xs: StepInputs, *, ddr_attribution: bool = False,
                gated: bool = False, wpack0=None, qfun=None, mlp_lr=None,
                mlp_dims=None, mlp_feats: str = "sense"):
    """Scan :func:`fused_step` over a whole episode (pure XLA).

    ``xs`` leaves carry a leading (S,) axis; ``extrema0`` is the initial
    reward-extrema table ((4, n_accs), from ``rewards.init_reward_state``).
    Returns ``(qtable_final, ys)`` with ``ys`` the per-step
    ``(mode, state_idx, action, exec_cycles, offchip, reward)`` arrays.
    Under ``vmap`` over episodes the carry enters the scan batched
    (:func:`_per_episode`).

    With a packed MLP (``wpack0`` + the traced ``qfun`` flag,
    :mod:`repro.soc.nn`) the weights ride the scan carry next to the
    Q-table and the return becomes ``(qtable_final, wpack_final, ys)``.
    """
    geom, warm_cap = derive_geom(s)
    n_threads = xs.others.shape[-1]
    n_tiles = xs.tiles.shape[-1]
    rs0 = rewards.RewardState(extrema=extrema0)
    tbl0 = init_slot_table(n_threads, n_tiles)

    if wpack0 is None:
        def step(carry, x):
            qtable, rs, tbl = carry
            qtable, rs, tbl, y = fused_step(
                s, geom, warm_cap, learned, weights, qtable, rs, tbl, x,
                ddr_attribution=ddr_attribution, gated=gated)
            return (qtable, rs, tbl), y

        (qtable, _, _), y = jax.lax.scan(
            step, _per_episode((qtable0, rs0, tbl0), xs.u_explore), xs)
        return qtable, unpack_ys(y)

    def step_mlp(carry, x):
        qtable, rs, tbl, wpack = carry
        qtable, rs, tbl, wpack, y = fused_step(
            s, geom, warm_cap, learned, weights, qtable, rs, tbl, x,
            ddr_attribution=ddr_attribution, gated=gated, wpack=wpack,
            qfun=qfun, mlp_lr=mlp_lr, mlp_dims=mlp_dims,
            mlp_feats=mlp_feats)
        return (qtable, rs, tbl, wpack), y

    (qtable, _, _, wpack), y = jax.lax.scan(
        step_mlp, _per_episode((qtable0, rs0, tbl0, wpack0), xs.u_explore),
        xs)
    return qtable, wpack, unpack_ys(y)
