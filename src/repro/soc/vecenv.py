"""Vectorized SoC environment — the scale path of the reproduction.

Where ``soc.des`` is the fidelity path (host-Python event loop, one agent at
a time), this module lowers a whole :class:`~repro.soc.des.Application` to
static arrays once and then runs entire training episodes *inside* jit:

  * :func:`compile_app` traces an application into a flattened (dense,
    round-major) invocation schedule — phases/threads become arrays of
    ``(acc_id, footprint, tile mask, thread slot, phase id, concurrency
    mask)``.  Memory-tile striping uses the DES's rng protocol so that on
    single-thread applications the two paths see bit-identical inputs;
  * every policy family lowers into one :class:`PolicySpec` pytree — a
    per-(phase, thread, step) precomputed mode table, a ``learned`` flag
    and a (possibly placeholder) ``qlearn.QState`` — and
    :meth:`VecEnv.episode` is one ``lax.scan`` over the schedule consuming
    that spec: each step does sense (``core.state.observe``) -> select
    (epsilon-greedy Q, or the spec's precomputed mode, picked by a
    ``lax.select`` on ``learned``) -> ``memsys.invocation_perf_cached``
    timing -> reward (``core.rewards.evaluate``) -> ``core.qlearn``
    update, entirely jitted.  Because the spec is an ordinary pytree,
    *heterogeneous batches of policies* vmap along a spec axis
    (:meth:`VecEnv.episodes`, ``StackedVecEnv.episodes``) — the paper's
    design-time-vs-learned comparisons run as one call;
  * :meth:`VecEnv.train` scans episodes over training iterations, and the
    ``*_batched`` entry points ``vmap`` over (agents/seeds x reward
    weights), so the Fig. 6 reward-DSE and Fig. 8 training curves run as
    one batched call instead of N sequential DES runs;
  * a third ``vmap`` axis over **SoC configurations** lives in
    :mod:`repro.soc.stacked`: every episode/train closure here takes its
    per-SoC constants as a :class:`LaneParams` argument, so the stacked
    environment can pad K SoCs to a common shape and run them in one call
    (Fig. 9's seven SoCs x seeds x reward weights).

Scan-step hot path: the contention model needs each concurrent slot's
unconstrained ``(dram, llc)`` bytes/cycle demand, which depends only on the
slot's (mode, profile, footprint) — values that change exactly when that
slot issues a new invocation.  The step therefore keeps per-slot demand in
the scan carry and writes ("invalidates") only the slot it executes,
instead of recomputing ``memsys.dma_demand`` for every slot every step
(:func:`memsys.invocation_perf_cached` is the matching fast-path timing
signature; the self-contained one stays for the DES).  Construct
``VecEnv(..., demand_cache=False)`` to get the recompute-every-step path —
kept for the before/after comparison in ``benchmarks/vecenv_throughput.py``
and the cache-equivalence tests.

Concurrency model (the one deliberate approximation): threads of a phase
advance in lockstep *rounds*.  The invocations of round ``r`` are mutually
concurrent — thread ``t`` senses threads ``< t`` of its own round and
threads ``> t`` of round ``r-1`` — which mirrors the DES at time zero and
approximates it afterwards (the DES interleaves by continuous completion
times and serializes device collisions).  Phase wall time is the max over
threads of per-thread busy time; for single-thread phases both the
concurrency set and the wall clock are exactly the DES's, which is what
``tests/test_vecenv_equivalence.py`` pins.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qlearn, rewards, state as cstate
from repro.core.modes import CoherenceMode, N_MODES
from repro.core.policies import EXTRA_SMALL_THRESHOLD
from repro.core.state import CacheGeometry
from repro.soc import faults as fault_mod
from repro.soc import traffic as traffic_mod
from repro.soc.accelerators import AccProfile, profile_matrix, resolve_profiles
from repro.soc.config import SoCConfig
from repro.soc.des import Application, SoCSimulator, stripe_tiles
from repro.soc.memsys import (SoCStatic, invocation_perf,
                              invocation_perf_cached, warmth_after)


class Schedule(NamedTuple):
    """Static per-step arrays of a compiled application (scan xs).

    Schedules are dense — every row is a real invocation (compile_app
    skips finished threads rather than padding rounds).  The stacked
    multi-SoC path pads lanes to a common length; ``valid`` is False on
    those padding rows (compile_app emits all-True)."""

    acc_id: jnp.ndarray      # (S,) int32
    footprint: jnp.ndarray   # (S,) float32 bytes
    tiles: jnp.ndarray       # (S, n_tiles) bool — memory-tile striping
    thread: jnp.ndarray      # (S,) int32 thread slot within the phase
    phase_id: jnp.ndarray    # (S,) int32
    fresh: jnp.ndarray       # (S,) bool — thread's first invocation in phase
    others: jnp.ndarray      # (S, T) bool — concurrently-active thread slots
    valid: jnp.ndarray       # (S,) bool — False marks stacked-padding rows


class LaneParams(NamedTuple):
    """Per-SoC constants threaded through the episode closures.

    A single :class:`VecEnv` closes over one of these; the stacked
    multi-SoC environment (:mod:`repro.soc.stacked`) stacks one per SoC
    along a leading axis and ``vmap``s the same closures over it."""

    pmat: jnp.ndarray        # (n_accs, F) accelerator profile matrix
    masks: jnp.ndarray       # (n_accs, N_MODES) action availability
    static: SoCStatic        # scalar leaves ((K,) arrays when stacked)


@dataclasses.dataclass(frozen=True)
class CompiledApp:
    """An Application lowered to static arrays plus host-side metadata."""

    name: str
    schedule: Schedule
    n_phases: int
    n_threads: int           # max thread slots across phases
    n_steps: int             # total (real, non-padding) invocations
    phase_names: tuple


def compile_app(app: Application, soc: SoCConfig, seed: int = 0) -> CompiledApp:
    """Trace ``app`` into a flattened, round-major invocation schedule.

    A thread's looped chain is unrolled; round ``r`` holds each thread's
    ``r``-th invocation.  The per-step concurrency mask encodes the lockstep
    overlap structure described in the module docstring.
    """
    rng = np.random.default_rng(seed)
    n_tiles = soc.n_mem_tiles
    max_threads = max((len(ph.threads) for ph in app.phases), default=1)

    rows: list[tuple] = []
    for ph_i, phase in enumerate(app.phases):
        progs = []
        for th in phase.threads:
            seq = []
            for _ in range(th.loops):
                seq.extend(th.chain)
            progs.append(seq)
        n_rounds = max((len(p) for p in progs), default=0)
        started = [False] * len(progs)
        for r in range(n_rounds):
            for t, prog in enumerate(progs):
                if r >= len(prog):
                    continue
                inv = prog[r]
                tiles = stripe_tiles(rng, n_tiles, inv.footprint)
                others = np.zeros(max_threads, bool)
                for j, pj in enumerate(progs):
                    if j == t:
                        continue
                    if j < t:          # already issued round r
                        others[j] = r < len(pj)
                    else:              # still running round r-1
                        others[j] = r >= 1 and (r - 1) < len(pj)
                rows.append((inv.acc_id, inv.footprint, tiles, t, ph_i,
                             not started[t], others))
                started[t] = True

    if not rows:
        raise ValueError(f"application {app.name!r} has no invocations")
    sched = Schedule(
        acc_id=jnp.asarray([r[0] for r in rows], jnp.int32),
        footprint=jnp.asarray([r[1] for r in rows], jnp.float32),
        tiles=jnp.asarray(np.stack([r[2] for r in rows])),
        thread=jnp.asarray([r[3] for r in rows], jnp.int32),
        phase_id=jnp.asarray([r[4] for r in rows], jnp.int32),
        fresh=jnp.asarray([r[5] for r in rows]),
        others=jnp.asarray(np.stack([r[6] for r in rows])),
        valid=jnp.ones((len(rows),), bool),
    )
    return CompiledApp(
        name=app.name, schedule=sched, n_phases=len(app.phases),
        n_threads=max_threads, n_steps=len(rows),
        phase_names=tuple(ph.name for ph in app.phases))


def stack_schedules(compiled: Sequence[CompiledApp]) -> Schedule:
    """Stack same-shape compiled apps along a leading axis (scan over
    training iterations, each with its own tile-striping seed)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[c.schedule for c in compiled])


class EpisodeResult(NamedTuple):
    """Per-phase metrics plus per-invocation traces of one episode."""

    phase_time: jnp.ndarray      # (P,) seconds of wall clock
    phase_offchip: jnp.ndarray   # (P,) off-chip line accesses
    mode: jnp.ndarray            # (S,) int32 chosen coherence mode
    state_idx: jnp.ndarray       # (S,) int32 sensed Table-3 state
    exec_time: jnp.ndarray       # (S,) float32 cycles
    offchip: jnp.ndarray         # (S,) float32 line accesses
    reward: jnp.ndarray          # (S,) float32

    @property
    def total_time(self):
        return jnp.sum(self.phase_time)

    @property
    def total_offchip(self):
        return jnp.sum(self.phase_offchip)


def normalized_metrics(res: EpisodeResult, base: EpisodeResult,
                       phase_mask=None):
    """Per-phase geomean (time, offchip) normalized to a baseline episode —
    the paper's Fixed-NON_COH normalization (orchestrator._geomean_ratio).

    ``phase_mask`` (same shape as ``res.phase_time``) restricts the geomean
    to real phases when lanes of a stacked multi-SoC batch were padded to a
    common phase count."""
    lt = jnp.log(jnp.maximum(
        res.phase_time / jnp.maximum(base.phase_time, 1e-30), 1e-12))
    lm = jnp.log(jnp.maximum(
        (res.phase_offchip + 1.0)
        / jnp.maximum(base.phase_offchip + 1.0, 1e-30), 1e-12))
    if phase_mask is None:
        return jnp.exp(jnp.mean(lt)), jnp.exp(jnp.mean(lm))
    w = phase_mask.astype(lt.dtype)
    n = jnp.maximum(jnp.sum(w), 1.0)
    return jnp.exp(jnp.sum(lt * w) / n), jnp.exp(jnp.sum(lm * w) / n)


def _manual_select(s: SoCStatic, footprint, active_modes, active_fp, avail):
    """Paper Algorithm 1 as pure jnp (mirrors policies.ManualPolicy)."""
    active = active_modes >= 0
    n_cd = jnp.sum(active & (active_modes == CoherenceMode.COH_DMA))
    n_fc = jnp.sum(active & (active_modes == CoherenceMode.FULLY_COH))
    n_nc = jnp.sum(active & (active_modes == CoherenceMode.NON_COH_DMA))
    l2 = s.l2_bytes
    llc = s.llc_slice_bytes * s.n_mem_tiles
    mode = jnp.where(
        footprint <= EXTRA_SMALL_THRESHOLD,
        CoherenceMode.FULLY_COH,
        jnp.where(
            footprint <= l2,
            jnp.where(n_cd > n_fc, CoherenceMode.FULLY_COH,
                      CoherenceMode.COH_DMA),
            jnp.where(
                footprint + active_fp > llc,
                CoherenceMode.NON_COH_DMA,
                jnp.where(n_nc >= 2, CoherenceMode.LLC_COH_DMA,
                          CoherenceMode.COH_DMA))))
    return jnp.where(avail[mode], mode, CoherenceMode.NON_COH_DMA)


class PolicySpec(NamedTuple):
    """One lowered policy — the single episode currency of every backend.

    Every policy family (fixed homogeneous/heterogeneous, manual, random,
    Q) lowers into this pytree via ``core.policies.Policy.lower``; the
    unified episode consumes nothing else.  Leaves may carry leading batch
    axes (policy batches, SoC lanes), so heterogeneous *batches of
    policies* are just stacked specs (:func:`stack_specs`).

    * ``modes`` — ``(S,)`` int32, the per-(phase, thread, step) mode table.
      For fixed policies it is ``assignment[acc_id[step]]``; for the manual
      heuristic the whole deterministic Algorithm-1 recursion is
      precomputed against the schedule (:func:`precompute_manual_modes`).
      Ignored (zeros) when ``learned``.
    * ``learned`` — ``()`` bool.  True selects epsilon-greedy Q actions via
      ``lax.select``; the non-taken branch is a few-flop row gather, so
      heterogeneous batches pay negligible dead-branch cost and XLA prunes
      nothing load-bearing when a batch is homogeneous.
    * ``qstate`` — the agent (trains in place when not frozen).  Non-
      learned specs carry ``qlearn.frozen_qstate()``: frozen makes the
      in-scan update a bitwise no-op, so one step serves every family.
    * ``qfun`` / ``mlp`` — the function-approximation branch
      (:mod:`repro.soc.nn`).  ``None`` (the default) is the tabular
      treedef every existing call site produces — those paths compile
      exactly the code they compiled before.  An MLP-lowered spec
      (:func:`mlp_policy_spec`) carries ``qfun=True`` plus the
      :class:`~repro.soc.nn.MLPQState`; the episode then selects from
      ``where(qfun, forward(wpack, features), qtable[state])`` and
      applies the semi-gradient TD update to the weight pack instead of
      the table.  Table specs that must share a treedef with MLP specs
      (stacked/heterogeneous batches) attach a frozen dead-branch
      placeholder via :func:`attach_placeholder_mlp` — ``qfun=False``
      keeps their episode results bitwise-identical to the bare spec.
    """

    modes: jnp.ndarray       # (S,) int32 precomputed per-step modes
    learned: jnp.ndarray     # () bool — Q-selection vs mode-table lookup
    qstate: qlearn.QState
    qfun: jnp.ndarray | None = None   # () bool — MLP Q-function selection
    mlp: object | None = None         # repro.soc.nn.MLPQState | None


def stack_specs(specs: Sequence[PolicySpec]) -> PolicySpec:
    """Stack lowered specs along a new leading policy axis (mixed families
    welcome — that axis is what ``episodes`` vmaps over)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *specs)


def _mask_modes(masks: jnp.ndarray, acc_id: jnp.ndarray,
                action: jnp.ndarray) -> jnp.ndarray:
    """Per-step availability fallback (unavailable -> NON_COH_DMA)."""
    avail = masks[acc_id]                                # (S, N_MODES)
    ok = jnp.take_along_axis(
        avail, action[:, None].astype(jnp.int32), axis=1)[:, 0]
    return jnp.where(ok, action,
                     int(CoherenceMode.NON_COH_DMA)).astype(jnp.int32)


def fixed_policy_spec(params: LaneParams, sched: Schedule,
                      fixed_modes) -> PolicySpec:
    """Lower a per-accelerator mode assignment (scalar broadcasts) into a
    per-step mode table."""
    n_accs = params.masks.shape[0]
    fm = jnp.broadcast_to(jnp.asarray(fixed_modes, jnp.int32), (n_accs,))
    return PolicySpec(
        modes=_mask_modes(params.masks, sched.acc_id, fm[sched.acc_id]),
        learned=jnp.zeros((), bool),
        qstate=qlearn.frozen_qstate())


def precompute_manual_modes(params: LaneParams,
                            sched: Schedule) -> jnp.ndarray:
    """Replay paper Algorithm 1 against a schedule, off the hot path.

    Manual selection depends only on the concurrent slots' (mode,
    footprint) — a deterministic recursion over the static schedule — so
    the whole mode table precomputes in one cheap ``lax.scan`` (no timing
    model, no reward).  The slot-table evolution (including ``valid``
    gating of stacked padding rows) mirrors the episode's exactly, which
    is what makes the lowered episode bitwise-identical to the old inline
    manual kind (``tests/test_policy_spec.py``)."""
    masks, s = params.masks, params.static
    T = sched.others.shape[-1]

    def step(tbl, x):
        tbl_mode, tbl_fp = tbl
        avail = masks[x.acc_id]
        omask = x.others & (tbl_mode >= 0)
        omodes = jnp.where(omask, tbl_mode, -1)
        ofps = jnp.where(omask, tbl_fp, 0.0)
        action = _manual_select(s, x.footprint, omodes, jnp.sum(ofps), avail)
        mode = jnp.where(avail[action], action,
                         CoherenceMode.NON_COH_DMA).astype(jnp.int32)
        new = (tbl_mode.at[x.thread].set(mode),
               tbl_fp.at[x.thread].set(x.footprint))
        new = jax.tree_util.tree_map(
            lambda n, o: jnp.where(x.valid, n, o), new, tbl)
        return new, mode

    tbl0 = (jnp.full((T,), -1, jnp.int32), jnp.zeros((T,), jnp.float32))
    _, modes = jax.lax.scan(step, tbl0, sched)
    return modes


_precompute_manual_modes = jax.jit(precompute_manual_modes)


def manual_policy_spec(params: LaneParams, sched: Schedule) -> PolicySpec:
    """Lower paper Algorithm 1 into a precomputed per-step mode table."""
    return PolicySpec(modes=_precompute_manual_modes(params, sched),
                      learned=jnp.zeros((), bool),
                      qstate=qlearn.frozen_qstate())


def learned_policy_spec(qstate: qlearn.QState,
                        sched: Schedule) -> PolicySpec:
    """Lower a Q agent (mode table is dead weight — zeros)."""
    return PolicySpec(modes=jnp.zeros_like(sched.acc_id),
                      learned=jnp.ones((), bool), qstate=qstate)


def mlp_policy_spec(mlp, sched: Schedule) -> PolicySpec:
    """Lower a function-approximation agent (:class:`repro.soc.nn.
    MLPQState`) — the neural analogue of :func:`learned_policy_spec`.

    The tabular slot carries a frozen placeholder (its in-scan update is
    a bitwise no-op and the episode's write guard keeps the table
    untouched on ``qfun`` specs), so the same unified step serves both
    agent families."""
    return PolicySpec(modes=jnp.zeros_like(sched.acc_id),
                      learned=jnp.zeros((), bool),
                      qstate=qlearn.frozen_qstate(),
                      qfun=jnp.ones((), bool), mlp=mlp)


def attach_placeholder_mlp(spec: PolicySpec, cfg=None) -> PolicySpec:
    """Give a table-lowered spec the MLP treedef without the MLP.

    Stacking heterogeneous specs (:func:`stack_specs`) needs a common
    pytree structure, so table specs that batch next to MLP specs carry
    a frozen zero-lr placeholder with ``qfun=False``.  The placeholder
    branch is dead — selection takes the table row, the TD gate is
    False, and the merged decay schedule reduces to the table's — so
    episode results are bitwise-identical to the bare spec (pinned by
    ``tests/test_policy_spec.py``)."""
    from repro.soc import nn as socnn
    return spec._replace(
        qfun=jnp.zeros((), bool),
        mlp=socnn.frozen_mlp_qstate(cfg or socnn.MLPConfig()))


def build_episode_fn(n_phases: int, n_threads: int,
                     cycle_time: float, demand_cache: bool = True,
                     gated: bool = False, presample_noise: bool = True,
                     ddr_attribution: bool = False,
                     fused: bool = False, debug_finite: bool = False,
                     kernel: bool | None = None):
    """Build THE jit-compatible episode function for a schedule geometry.

    There is one episode; policies differ only in the :class:`PolicySpec`
    they lowered into.  The returned ``episode(params, sched, spec, cfg,
    weights, key)`` closure takes its per-SoC constants as a
    :class:`LaneParams` argument so it can serve both a single
    :class:`VecEnv` (params closed over by the caller) and the stacked
    multi-SoC environment (params vmapped over a leading lane axis);
    batching over *policies* is just a vmap over the spec (and key) axes.

    ``demand_cache`` selects the fast path: per-slot (dram, llc) demand
    lives in the scan carry and only the executing slot's entry is
    rewritten each step.  ``presample_noise`` draws the whole episode's
    select noise in one batched call instead of splitting keys inside the
    scan; ``False`` restores the original per-step threefry (kept, with
    ``demand_cache=False``, as the pre-optimization reference the
    throughput benchmark measures against).  ``gated`` adds padding-row
    gating for stacked schedules: a ``valid=False`` row leaves the
    Q-table, reward extrema and slot table untouched (padding rows sit at
    the tail of a lane, so the PRNG stream of real rows is unaffected).
    ``ddr_attribution`` feeds the reward the DES's prorated per-tile DDR
    attribution instead of the invocation's true off-chip count (requires
    ``demand_cache``; traces and phase metrics stay ground-truth).

    ``fused`` swaps the inner loop for the fused-step lowering
    (:mod:`repro.kernels.soc_step`): one Q-row gather shared between
    selection and update, the (epsilon, alpha) decay and step-counter
    increments precomputed outside the scan, visits/step reconstructed
    from the trace afterwards, and per-accelerator profile/mask rows
    pregathered into the xs — a Pallas kernel on accelerator backends, a
    single tight XLA scan on CPU.  Results are bitwise-identical to the
    unfused reference step (pinned by the equivalence tests); it requires
    the ``demand_cache`` + ``presample_noise`` fast path.  ``kernel`` is
    :func:`repro.kernels.soc_step.ops.fused_episode`'s lowering choice
    (``None``: the platform's); batched callers take it from
    :func:`episode_lowering`.

    The episode closure takes an optional trailing :class:`~repro.soc.
    faults.FaultSpec` — pre-sampled per-step perturbation rows join the
    scan xs and flow into the timing model (``soc.faults`` documents the
    model and the zero-spec bitwise-identity contract).  ``debug_finite``
    adds episode-exit finiteness tripwires on the reward trace and the
    trained Q-table (``qlearn.debug_finite_check``); off by default
    because the host callback forces a device sync per episode.
    """
    if ddr_attribution and not demand_cache:
        raise ValueError("ddr_attribution requires the demand_cache step")
    if fused and not (demand_cache and presample_noise):
        raise ValueError(
            "fused_step requires demand_cache=True and presample_noise=True")
    if fused:
        return _build_fused_episode_fn(n_phases, n_threads, cycle_time,
                                       gated, ddr_attribution, debug_finite,
                                       kernel)
    T, P = n_threads, n_phases

    def episode(params: LaneParams, sched: Schedule, spec: PolicySpec, cfg,
                weights, key, faults: fault_mod.FaultSpec | None = None):
        qs0 = spec.qstate
        mlp = spec.mlp
        if mlp is not None:
            if not (demand_cache and presample_noise):
                raise ValueError(
                    "MLP PolicySpecs require the demand_cache + "
                    "presample_noise fast path (the sense features read "
                    "the cached per-slot demand)")
            from repro.soc import nn as socnn
            mlp_dims = socnn.mlp_dims(mlp.cfg)
        pmat, masks, s = params.pmat, params.masks, params.static
        n_accs = pmat.shape[0]
        n_tiles = sched.tiles.shape[-1]
        geom = CacheGeometry(
            l2_bytes=s.l2_bytes, llc_slice_bytes=s.llc_slice_bytes,
            n_mem_tiles=s.n_mem_tiles)
        warm_cap = (s.llc_slice_bytes * s.n_mem_tiles
                    + s.n_cpus * s.l2_bytes)

        def step(carry, xs):
            x, pre_mode, noise, fr = xs
            if mlp is not None:
                qs, rs, tbl, mw, mstep = carry
            elif presample_noise:
                qs, rs, tbl = carry
            else:
                qs, rs, key, tbl = carry
            if demand_cache:
                (tbl_mode, tbl_fp, tbl_tiles, warm, tbl_dram, tbl_llc,
                 tbl_fpt) = tbl
            else:
                tbl_acc, tbl_mode, tbl_fp, tbl_tiles, warm = tbl
            acc = x.acc_id
            profile = pmat[acc]
            avail = masks[acc]

            # ---- sense (paper §4.1): fixed-size active-set snapshot.
            omask = x.others & (tbl_mode >= 0)
            omodes = jnp.where(omask, tbl_mode, -1)
            ofps = jnp.where(omask, tbl_fp, 0.0)
            otiles = tbl_tiles & omask[:, None]
            # fp/|tiles| rides the carry next to the demand cache (written
            # only on slot writes); supplying it is bitwise-equal to the
            # in-observe division.
            ofpt = (jnp.where(omask, tbl_fpt, 0.0) if demand_cache
                    else None)
            state_idx = cstate.observe(
                active_modes=omodes, active_footprints=ofps,
                needed_tiles=otiles, target_tiles=x.tiles,
                target_footprint=x.footprint, geom=geom,
                active_fp_per_tile=ofpt)

            warm_t = jnp.where(x.fresh, 1.0, warm[x.thread])
            if demand_cache:
                odram = jnp.where(omask, tbl_dram, 0.0)
                ollc = jnp.where(omask, tbl_llc, 0.0)
            else:
                oprofiles = jnp.where(
                    omask[:, None], pmat[jnp.maximum(tbl_acc, 0)], 0.0)

            def env_half(action):
                """Actuate + time + evaluate for a chosen action (the
                environment half of qlearn.episode_step)."""
                # Degradation safety: a non-finite footprint (fault-
                # corrupted input) forces the non-coherent fallback mode,
                # matching the fused step's guard.  Finite footprints make
                # the extra conjunct a constant True — bitwise no-op.
                mode = jnp.where(avail[action] & jnp.isfinite(x.footprint),
                                 action,
                                 CoherenceMode.NON_COH_DMA).astype(jnp.int32)
                if demand_cache:
                    m, aux = invocation_perf_cached(
                        mode, profile, x.footprint, x.tiles, omodes, odram,
                        ollc, ofps, otiles, warm_t, s, fault=fr)
                else:
                    m, aux = invocation_perf(
                        mode, profile, x.footprint, x.tiles, omodes,
                        oprofiles, ofps, otiles, warm_t, s, fault=fr)
                off_reward = m.offchip_accesses
                if ddr_attribution:
                    # Paper §4.1(4): the monitors attribute the per-tile
                    # DDR counter delta over the invocation's window by
                    # footprint share — my prorated slice of my own plus
                    # the concurrent set's traffic on my tiles (exact when
                    # running alone; "attribution noise" under sharing).
                    myt = x.tiles.astype(jnp.float32)
                    n_my = jnp.maximum(jnp.sum(myt), 1.0)
                    o_nt = jnp.maximum(
                        jnp.sum(otiles.astype(jnp.float32), -1), 1.0)
                    my_fp_t = (x.footprint / n_my) * myt
                    o_fp_t = jnp.sum(ofpt[:, None] * otiles, 0)
                    share = my_fp_t / jnp.maximum(my_fp_t + o_fp_t, 1e-9)
                    my_bpt = (m.offchip_accesses * s.line / n_my) * myt
                    o_bpt = jnp.sum(
                        ((odram * m.exec_time) / o_nt)[:, None] * otiles, 0)
                    off_reward = (jnp.sum(share * (my_bpt + o_bpt))
                                  / s.line)
                meas = rewards.Measurement(
                    exec_time=m.exec_time, comm_cycles=m.comm_cycles,
                    total_cycles=m.total_cycles,
                    offchip_accesses=off_reward,
                    footprint=x.footprint)
                r, rs_new, _ = rewards.evaluate(rs, acc, meas, weights)
                return r, (mode, m.exec_time, m.offchip_accesses, rs_new,
                           aux["demand_dram"], aux["demand_llc"])

            # ---- decide: epsilon-greedy Q vs the spec's precomputed mode
            # (frozen placeholder qstates make the update a bitwise no-op
            # for non-learned specs, so there is exactly one step).
            if mlp is not None:
                # Function-approximation branch: the selected row is
                # where(qfun, forward(wpack, features), qtable[state]),
                # with (eps, alpha) read off the MERGED schedule — the
                # carried counter starts at where(qfun, mlp.step,
                # qs.step) and advances like the live agent's, so both
                # families share one decay stream (bitwise-equal to the
                # fused lowering's decay_arrays precomputation, and to
                # select_presampled on qfun=False specs).
                feats = socnn.step_features(
                    mlp.cfg.features, s, state_idx, footprint=x.footprint,
                    tiles=x.tiles, omask=omask, omodes=omodes, ofps=ofps,
                    odram=odram, warm_t=warm_t, profile=profile,
                    slack=jnp.float32(0.0), reuse=jnp.float32(0.0))
                raw_row = qs.qtable[state_idx]
                row_sel = jnp.where(
                    spec.qfun, socnn.forward_packed(mw, feats, mlp_dims),
                    raw_row)
                frozen_eff = jnp.where(spec.qfun, mlp.frozen, qs.frozen)
                eps_eff, alpha_eff = qlearn.schedule(cfg, mstep)
                eps_eff = jnp.where(frozen_eff, 0.0, eps_eff)
                alpha_eff = jnp.where(frozen_eff, 0.0, alpha_eff)
                q_action = qlearn.row_select_presampled(row_sel, eps_eff,
                                                        noise, avail)
                learned_eff = spec.learned | spec.qfun
            elif presample_noise:
                q_action = qlearn.select_presampled(qs, cfg, state_idx,
                                                    noise, avail)
                learned_eff = spec.learned
            else:
                key, k_sel = jax.random.split(key)
                q_action = qlearn.select(qs, cfg, state_idx, k_sel, avail)
                learned_eff = spec.learned
            action = jax.lax.select(learned_eff, q_action, pre_mode)
            r, (mode, exec_c, off, rs_new, d_dram, d_llc) = env_half(action)
            qs_new = qlearn.update(qs, cfg, state_idx, action, r)
            if mlp is not None:
                # Semi-gradient TD on the weight pack (gate self-selects
                # inside td_update_packed, so no keep-gating below); the
                # table update above is a frozen no-op on qfun specs.
                live = x.valid if gated else jnp.ones((), bool)
                upd_gate = (spec.qfun & x.valid) if gated else spec.qfun
                mw_new = socnn.td_update_packed(
                    mw, feats, action, r, alpha_eff * mlp.lr, mlp_dims,
                    upd_gate)
                mstep_new = mstep + jnp.where(live & ~frozen_eff, 1, 0
                                              ).astype(jnp.int32)

            # ---- bookkeeping: thread slot table + inter-stage warmth +
            # (fast path) this slot's cached demand.
            if demand_cache:
                tbl_new = (
                    tbl_mode.at[x.thread].set(mode),
                    tbl_fp.at[x.thread].set(x.footprint),
                    tbl_tiles.at[x.thread].set(x.tiles),
                    warm.at[x.thread].set(
                        warmth_after(mode, x.footprint, warm_cap)),
                    tbl_dram.at[x.thread].set(d_dram),
                    tbl_llc.at[x.thread].set(d_llc),
                    tbl_fpt.at[x.thread].set(
                        x.footprint / jnp.maximum(jnp.sum(x.tiles), 1)))
            else:
                tbl_new = (
                    tbl_acc.at[x.thread].set(acc),
                    tbl_mode.at[x.thread].set(mode),
                    tbl_fp.at[x.thread].set(x.footprint),
                    tbl_tiles.at[x.thread].set(x.tiles),
                    warm.at[x.thread].set(
                        warmth_after(mode, x.footprint, warm_cap)))

            if gated:
                def keep(new, old):
                    return jnp.where(x.valid, new, old)
                qs_new = jax.tree_util.tree_map(keep, qs_new, qs)
                rs_new = jax.tree_util.tree_map(keep, rs_new, rs)
                tbl_new = jax.tree_util.tree_map(keep, tbl_new, tbl)

            y = (mode, state_idx, exec_c, off, r)
            if mlp is not None:
                return (qs_new, rs_new, tbl_new, mw_new, mstep_new), y
            if presample_noise:
                return (qs_new, rs_new, tbl_new), y
            return (qs_new, rs_new, key, tbl_new), y

        if demand_cache:
            tbl0 = (jnp.full((T,), -1, jnp.int32),
                    jnp.zeros((T,), jnp.float32),
                    jnp.zeros((T, n_tiles), bool),
                    jnp.ones((T,), jnp.float32),
                    jnp.zeros((T,), jnp.float32),
                    jnp.zeros((T,), jnp.float32),
                    jnp.zeros((T,), jnp.float32))
        else:
            tbl0 = (jnp.full((T,), -1, jnp.int32),
                    jnp.full((T,), -1, jnp.int32),
                    jnp.zeros((T,), jnp.float32),
                    jnp.zeros((T, n_tiles), bool),
                    jnp.ones((T,), jnp.float32))
        # Episode randomness is pre-sampled in one batched threefry call —
        # per-step split/categorical inside the scan would dominate the
        # step cost (see qlearn.SelectNoise).  The draw matches the old
        # q-kind episode bit for bit; non-learned specs discard the
        # selection, so their results are key-independent.
        n_steps = sched.acc_id.shape[0]
        with jax.named_scope("cohm_presample"):
            if presample_noise:
                noise = qlearn.sample_select_noise(
                    key, (n_steps,), masks.shape[-1])
            else:
                noise = qlearn.SelectNoise(
                    u_explore=jnp.zeros((n_steps,), jnp.float32),
                    g_pick=jnp.zeros((n_steps, 0), jnp.float32),
                    g_tie=jnp.zeros((n_steps, 0), jnp.float32))
            # Per-step fault rows are pre-sampled from the spec's OWN key
            # (soc.faults), so the episode's main key stream is untouched
            # and ``faults=None`` stays bitwise-identical to today's path
            # (None scans as an empty pytree — the step sees fr is None).
            frows = (None if faults is None
                     else fault_mod.sample_fault_arrays(faults,
                                                        sched.acc_id))
        rs0 = rewards.init_reward_state(n_accs)
        if mlp is not None:
            carry = (qs0, rs0, tbl0, mlp.wpack,
                     jnp.where(spec.qfun, mlp.step, qs0.step))
        else:
            carry = ((qs0, rs0, tbl0) if presample_noise
                     else (qs0, rs0, key, tbl0))
        carry, ys = jax.lax.scan(step, carry,
                                 (sched, spec.modes, noise, frows))
        mode, state_idx, exec_c, off, rew = ys
        if debug_finite:
            qlearn.debug_finite_check(
                "vecenv.episode", reward=rew, qtable=carry[0].qtable)

        # Per-phase wall clock: max over threads of per-thread busy time
        # (threads chain serially; phases are sequential).  Padding rows
        # contribute nothing.
        secs = jnp.where(sched.valid, exec_c, 0.0) * cycle_time
        off_real = jnp.where(sched.valid, off, 0.0)
        per_thread = jnp.zeros((P, T), secs.dtype).at[
            sched.phase_id, sched.thread].add(secs)
        phase_time = jnp.max(per_thread, axis=1)
        phase_off = jnp.zeros((P,), off_real.dtype).at[
            sched.phase_id].add(off_real)
        res = EpisodeResult(
            phase_time=phase_time, phase_offchip=phase_off, mode=mode,
            state_idx=state_idx, exec_time=exec_c, offchip=off,
            reward=rew)
        if mlp is not None:
            # MLP-treedef specs return BOTH trained agents; the merged
            # counter only lands in the mlp when it drove the schedule.
            mlp_final = mlp._replace(
                wpack=carry[3],
                step=jnp.where(spec.qfun, carry[4], mlp.step))
            return (carry[0], mlp_final), res
        return carry[0], res

    return episode


def _build_fused_episode_fn(n_phases: int, n_threads: int,
                            cycle_time: float, gated: bool,
                            ddr_attribution: bool,
                            debug_finite: bool = False,
                            kernel: bool | None = None):
    """The fused-step lowering of :func:`build_episode_fn` (its ``fused``
    paragraph documents the semantics).  The step itself lives in
    :mod:`repro.kernels.soc_step`; this closure owns the episode-level
    pre/post work: noise + decay-schedule precomputation, the profile/mask
    pregather, visits/step replay, and the per-phase metric tail (shared
    verbatim with the unfused episode).  Imported lazily to keep
    ``soc.vecenv`` importable without the kernels package on odd installs.
    The pre-sampling runs under the ``cohm_presample`` name scope, the
    step under ``cohm_step``.
    """
    from repro.kernels.soc_step import ops as soc_step_ops
    from repro.kernels.soc_step.ref import StepInputs

    T, P = n_threads, n_phases

    def episode(params: LaneParams, sched: Schedule, spec: PolicySpec, cfg,
                weights, key, faults: fault_mod.FaultSpec | None = None):
        qs0 = spec.qstate
        mlp = spec.mlp
        pmat, masks, s = params.pmat, params.masks, params.static
        n_accs = pmat.shape[0]
        n_steps = sched.acc_id.shape[0]

        with jax.named_scope("cohm_presample"):
            # Same one-call noise protocol as the unfused episode —
            # identical key consumption, so fused and unfused draw
            # identical variates.
            noise = qlearn.sample_select_noise(key, (n_steps,),
                                               masks.shape[-1])
            # Counter increments the in-scan update would apply: zero on
            # frozen agents and (gated schedules) on padding rows.
            # MLP-treedef specs precompute the MERGED schedule — the live
            # agent's (step0, frozen) drive the decay, and the increments
            # are split afterwards so each family's counter only advances
            # when it drove the episode.  With qfun=False (placeholder
            # MLP) the merge selects the table's values, so
            # eps_t/alpha_t/inc are bitwise the tabular ones.
            live = sched.valid if gated else jnp.ones_like(sched.valid)
            if mlp is None:
                step0_eff, frozen_eff = qs0.step, qs0.frozen
            else:
                step0_eff = jnp.where(spec.qfun, mlp.step, qs0.step)
                frozen_eff = jnp.where(spec.qfun, mlp.frozen, qs0.frozen)
            inc = (live & ~frozen_eff).astype(jnp.int32)
            eps_t, alpha_t = qlearn.decay_arrays(cfg, step0_eff,
                                                 frozen_eff, inc)
            # Fault rows ride four trailing xs columns (same pre-sampled
            # draw as the unfused scan, so the lowerings stay
            # bitwise-equal).
            frow = {}
            if faults is not None:
                fr = fault_mod.sample_fault_arrays(faults, sched.acc_id)
                frow = dict(f_exec=fr.exec_scale, f_ddr=fr.ddr_scale,
                            f_llc=fr.llc_extra, f_retry=fr.retry_cycles)
        xs = StepInputs(
            acc_id=sched.acc_id, footprint=sched.footprint,
            tiles=sched.tiles, thread=sched.thread, fresh=sched.fresh,
            others=sched.others, valid=sched.valid, pre_mode=spec.modes,
            profile=pmat[sched.acc_id], avail=masks[sched.acc_id],
            eps=eps_t, alpha=alpha_t, u_explore=noise.u_explore,
            g_pick=noise.g_pick, g_tie=noise.g_tie, **frow)
        if mlp is None:
            qtable, ys = soc_step_ops.fused_episode(
                s, spec.learned, weights, qs0.qtable,
                rewards.init_reward_state(n_accs).extrema, xs,
                ddr_attribution=ddr_attribution, gated=gated, kernel=kernel)
            inc_tbl = inc
        else:
            qtable, wpack, ys = soc_step_ops.fused_episode(
                s, spec.learned, weights, qs0.qtable,
                rewards.init_reward_state(n_accs).extrema, xs,
                ddr_attribution=ddr_attribution, gated=gated,
                qfun=spec.qfun, mlp=mlp, kernel=kernel)
            inc_tbl = jnp.where(spec.qfun, 0, inc)
        mode, state_idx, action, exec_c, off, rew = ys
        qs_final = qlearn.replay_visits(qs0, qtable, state_idx, action,
                                        inc_tbl)
        if debug_finite:
            qlearn.debug_finite_check(
                "vecenv.episode", reward=rew, qtable=qs_final.qtable)

        # Per-phase metric tail — identical to the unfused episode's.
        secs = jnp.where(sched.valid, exec_c, 0.0) * cycle_time
        off_real = jnp.where(sched.valid, off, 0.0)
        per_thread = jnp.zeros((P, T), secs.dtype).at[
            sched.phase_id, sched.thread].add(secs)
        phase_time = jnp.max(per_thread, axis=1)
        phase_off = jnp.zeros((P,), off_real.dtype).at[
            sched.phase_id].add(off_real)
        res = EpisodeResult(
            phase_time=phase_time, phase_offchip=phase_off, mode=mode,
            state_idx=state_idx, exec_time=exec_c, offchip=off,
            reward=rew)
        if mlp is not None:
            mlp_final = mlp._replace(
                wpack=wpack,
                step=mlp.step + jnp.sum(jnp.where(spec.qfun, inc, 0)))
            return (qs_final, mlp_final), res
        return qs_final, res

    return episode


class TrainCarry(NamedTuple):
    """Cross-iteration training state beyond the Q-state itself.

    Threading it explicitly (instead of a bare PRNG key) is what makes
    training *chunkable*: ``VecEnv.train_batched_checkpointed`` carries a
    ``(QState, TrainCarry)`` pair across host-side chunks and the resumed
    scan continues bitwise-exactly where the interrupted one stopped.

    * ``key`` — (2,) uint32 main episode key stream (split 3 ways per
      iteration, exactly as before the refactor);
    * ``it`` — () int32 global iteration index.  Fault-injected training
      folds it into the FaultSpec's own key so every iteration draws fresh
      drop coins without touching the main stream;
    * ``best`` — () float32 running best mean episode reward, feeding the
      reward-collapse watchdog (``qlearn.reward_watchdog``).
    """

    key: jnp.ndarray
    it: jnp.ndarray
    best: jnp.ndarray


def init_train_carry(key) -> TrainCarry:
    return TrainCarry(key=key, it=jnp.zeros((), jnp.int32),
                      best=jnp.full((), -jnp.inf, jnp.float32))


def build_train_fn(n_phases: int, n_threads: int, eval_shape,
                   cycle_time: float, demand_cache: bool = True,
                   gated: bool = False, presample_noise: bool = True,
                   ddr_attribution: bool = False, fused: bool = False,
                   debug_finite: bool = False, kernel: bool | None = None):
    """Build ``train_one(params, train_scheds, eval_sched, base, phase_mask,
    cfg, weights, carry0, q0, faults)``: a scan of training episodes over
    iterations, optionally evaluating the frozen policy each iteration
    against the NON_COH baseline (Fig. 8).  Like :func:`build_episode_fn`
    it is parameterized over :class:`LaneParams` so the stacked environment
    can vmap SoC lanes over it.

    ``carry0`` is a :class:`TrainCarry`; the function returns ``(qs,
    carry_out, hist)`` so chunked (checkpointed) training can resume
    mid-scan.  ``faults`` perturbs both the training and the evaluation
    episodes; its key is re-derived per iteration from ``carry.it``.
    ``kernel`` is :func:`build_episode_fn`'s.
    """
    episode = build_episode_fn(n_phases, n_threads, cycle_time,
                               demand_cache, gated, presample_noise,
                               ddr_attribution, fused, debug_finite, kernel)
    eval_episode = (build_episode_fn(eval_shape[0], eval_shape[1],
                                     cycle_time, demand_cache, gated,
                                     presample_noise, ddr_attribution,
                                     fused, debug_finite, kernel)
                    if eval_shape is not None else None)

    def train_one(params, train_scheds, eval_sched, base, phase_mask, cfg,
                  weights, carry0, q0, faults=None):
        def body(carry, sched_i):
            qs, tc = carry
            key, k_train, k_eval = jax.random.split(tc.key, 3)
            f_i = None
            if faults is not None:
                f_i = faults._replace(
                    key=jax.random.fold_in(faults.key, tc.it))
            qs, er = episode(params, sched_i,
                             learned_policy_spec(qs, sched_i), cfg,
                             weights, k_train, f_i)
            # Reward-collapse watchdog (qlearn.reward_watchdog): mean
            # per-invocation reward of the training episode vs the best
            # seen.  A no-op unless cfg.collapse_frac > 0.
            valid = sched_i.valid
            ep_r = (jnp.sum(jnp.where(valid, er.reward, 0.0))
                    / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0))
            qs, best = qlearn.reward_watchdog(cfg, qs, ep_r, tc.best)
            if eval_sched is not None:
                _, er2 = eval_episode(
                    params, eval_sched,
                    learned_policy_spec(qlearn.freeze(qs), eval_sched),
                    cfg, weights, k_eval, f_i)
                out = normalized_metrics(er2, base, phase_mask)
            else:
                out = (jnp.float32(0.0), jnp.float32(0.0))
            tc = TrainCarry(key=key, it=tc.it + 1, best=best)
            return (qs, tc), out

        (qs, tc), hist = jax.lax.scan(body, (q0, carry0), train_scheds)
        return qs, tc, hist

    return train_one


def episode_lowering(calls: collections.Counter, n_episodes: int,
                     fused: bool) -> bool | None:
    """The fused step's lowering for a call that runs ``n_episodes``
    episodes at once (:func:`repro.kernels.soc_step.ops.episode_kernel`),
    counted in ``calls`` as ``episode_kernel`` or ``episode_scan``.
    ``None`` for the unfused step, which has no lowering to choose."""
    if not fused:
        return None
    from repro.kernels.soc_step import ops as soc_step_ops
    kernel = soc_step_ops.episode_kernel(n_episodes)
    calls["episode_kernel" if kernel else "episode_scan"] += 1
    return kernel


class VecEnv:
    """Fully-jitted batched SoC environment over one SoC + accelerator set.

    Mirrors :class:`~repro.soc.des.SoCSimulator`'s construction (same
    profile resolution, action masks and timing constants) so the two paths
    are directly comparable; ``VecEnv.from_simulator`` shares an existing
    simulator's resolved profiles.

    ``demand_cache=True`` (the default) runs the carry-cached scan step;
    ``False`` recomputes every slot's demand each step (the pre-cache hot
    path, kept for benchmarking and equivalence tests — results are
    identical, see ``tests/test_vecenv_equivalence.py``).
    ``presample_noise=False`` additionally restores per-step RNG splitting;
    together with ``demand_cache=False`` that is the original (pre-
    optimization) scan step, the "before" of
    ``benchmarks/vecenv_throughput.py``.  ``ddr_attribution=True`` trains
    rewards on the DES's prorated DDR attribution instead of true
    per-invocation off-chip counts (measured in ``fig8_training``).

    ``fused_step`` selects the :mod:`repro.kernels.soc_step` episode
    lowering (shared Q-row gather, out-of-scan decay schedule, Q-table-only
    carry; a Pallas kernel on accelerator backends).  ``None`` (default)
    auto-enables it whenever the fast path it fuses is active
    (``demand_cache and presample_noise``) — results are bitwise-identical
    to the unfused step, so only benchmarks and equivalence tests pass an
    explicit ``False``.  The batched calls lower the fused step by their
    episode count (:func:`episode_lowering`) and count the lowering each
    took in ``calls``.
    """

    def __init__(self, soc: SoCConfig,
                 profiles: Sequence[AccProfile] | None = None,
                 seed: int = 0, flavor: str = "mixed",
                 cycle_time: float = 1e-8,
                 demand_cache: bool = True,
                 presample_noise: bool = True,
                 ddr_attribution: bool = False,
                 fused_step: bool | None = None,
                 debug_finite: bool = False):
        self.soc = soc
        rng = np.random.default_rng(seed)
        self.profiles = list(profiles) if profiles is not None else (
            resolve_profiles(soc.accelerators, rng, flavor))
        assert len(self.profiles) == soc.n_accs
        self.pmat = jnp.asarray(profile_matrix(self.profiles))
        self.static = SoCStatic.from_config(soc)
        self.geom = soc.geometry
        self.cycle_time = float(cycle_time)
        self.demand_cache = bool(demand_cache)
        self.presample_noise = bool(presample_noise)
        self.ddr_attribution = bool(ddr_attribution)
        if self.ddr_attribution and not self.demand_cache:
            raise ValueError("ddr_attribution requires demand_cache=True")
        if fused_step is None:
            fused_step = self.demand_cache and self.presample_noise
        elif fused_step and not (self.demand_cache
                                 and self.presample_noise):
            raise ValueError("fused_step requires demand_cache=True and "
                             "presample_noise=True")
        self.fused_step = bool(fused_step)
        self.debug_finite = bool(debug_finite)
        masks = np.ones((soc.n_accs, N_MODES), bool)
        for i in soc.no_private_cache:
            masks[i, CoherenceMode.FULLY_COH] = False
        self.masks = jnp.asarray(masks)
        self.params = LaneParams(pmat=self.pmat, masks=self.masks,
                                 static=self.static)
        self._episode_cache: dict = {}
        self._train_cache: dict = {}
        self.calls = collections.Counter()

    @classmethod
    def from_simulator(cls, sim: SoCSimulator,
                       cycle_time: float = 1e-8,
                       demand_cache: bool = True,
                       presample_noise: bool = True,
                       ddr_attribution: bool = False,
                       fused_step: bool | None = None,
                       debug_finite: bool = False) -> "VecEnv":
        return cls(sim.soc, profiles=sim.profiles, cycle_time=cycle_time,
                   demand_cache=demand_cache,
                   presample_noise=presample_noise,
                   ddr_attribution=ddr_attribution,
                   fused_step=fused_step,
                   debug_finite=debug_finite)

    # ------------------------------------------------------------ episode
    def _episode_fn(self, n_phases: int, n_threads: int,
                    kernel: bool | None = None):
        """Build (and cache) the spec-consuming episode closure (params
        pre-bound).  One closure per schedule geometry and lowering serves
        every policy family — the jit cache no longer keys on a policy
        kind."""
        cache_key = ("ep", n_phases, n_threads, kernel)
        if cache_key in self._episode_cache:
            return self._episode_cache[cache_key]
        base_fn = build_episode_fn(n_phases, n_threads,
                                   self.cycle_time, self.demand_cache,
                                   presample_noise=self.presample_noise,
                                   ddr_attribution=self.ddr_attribution,
                                   fused=self.fused_step,
                                   debug_finite=self.debug_finite,
                                   kernel=kernel)
        params = self.params

        def episode(sched, spec, cfg, weights, key, faults=None):
            return base_fn(params, sched, spec, cfg, weights, key, faults)

        self._episode_cache[cache_key] = episode
        return episode

    # -------------------------------------------------------- spec lowering
    def lower(self, compiled: CompiledApp, policy: str = "q",
              qstate: qlearn.QState | None = None,
              fixed_modes=None,
              cfg: qlearn.QConfig | None = None) -> PolicySpec:
        """Lower a policy-kind shorthand onto ``compiled``'s schedule.

        Prefer ``Policy.lower(env, compiled)`` on a real policy object;
        this keeps the string shorthand (`'q' | 'fixed' | 'manual'`) for
        tests and quick calls.  ``cfg`` shapes a fresh Q-state when
        ``policy='q'`` and no ``qstate`` is given (table shape and
        ``q_init`` must come from the cfg the episode will run with)."""
        if policy == "q":
            qstate = (qstate if qstate is not None
                      else qlearn.init_qstate(cfg or qlearn.QConfig()))
            return learned_policy_spec(qstate, compiled.schedule)
        if policy == "fixed":
            if fixed_modes is None:
                fixed_modes = CoherenceMode.NON_COH_DMA
            return fixed_policy_spec(self.params, compiled.schedule,
                                     fixed_modes)
        if policy == "manual":
            return manual_policy_spec(self.params, compiled.schedule)
        raise ValueError(f"unknown policy kind {policy!r}")

    # ----------------------------------------------------- public episodes
    def episode_spec(self, compiled: CompiledApp, spec: PolicySpec,
                     cfg: qlearn.QConfig | None = None,
                     weights: rewards.RewardWeights | None = None,
                     key=None,
                     faults: fault_mod.FaultSpec | None = None
                     ) -> tuple[qlearn.QState, EpisodeResult]:
        """Run one lowered :class:`PolicySpec` episode under jit.

        MLP-treedef specs (``spec.mlp is not None``) return ``((qstate,
        mlp), result)`` — both trained agents — instead of ``(qstate,
        result)``."""
        cfg = cfg or qlearn.QConfig()
        weights = weights or rewards.PAPER_DEFAULT_WEIGHTS
        key = key if key is not None else jax.random.PRNGKey(0)
        jit_key = ("jit", compiled.n_phases, compiled.n_threads)
        if jit_key not in self._episode_cache:
            self._episode_cache[jit_key] = jax.jit(self._episode_fn(
                compiled.n_phases, compiled.n_threads))
        return self._episode_cache[jit_key](
            compiled.schedule, spec, cfg, weights, key, faults)

    def episode(self, compiled: CompiledApp, *, policy: str = "q",
                qstate: qlearn.QState | None = None,
                cfg: qlearn.QConfig | None = None,
                fixed_modes=None,
                weights: rewards.RewardWeights | None = None,
                key=None,
                faults: fault_mod.FaultSpec | None = None
                ) -> tuple[qlearn.QState, EpisodeResult]:
        """Run one episode under jit (shorthand over :meth:`episode_spec`).
        ``policy``:

        * ``'q'`` — the Cohmeleon agent (``qstate`` trains in place unless
          frozen);
        * ``'fixed'`` — per-accelerator mode array (scalar broadcasts), the
          fixed-homogeneous/heterogeneous baselines;
        * ``'manual'`` — paper Algorithm 1.
        """
        spec = self.lower(compiled, policy, qstate=qstate,
                          fixed_modes=fixed_modes, cfg=cfg)
        return self.episode_spec(compiled, spec, cfg=cfg, weights=weights,
                                 key=key, faults=faults)

    def episodes(self, compiled: CompiledApp, specs: PolicySpec,
                 cfg: qlearn.QConfig | None = None,
                 weights: rewards.RewardWeights | None = None,
                 keys=None,
                 faults: fault_mod.FaultSpec | None = None) -> EpisodeResult:
        """A heterogeneous batch of lowered policies on one app, one call.

        ``specs`` leaves carry a leading (N,) policy axis
        (:func:`stack_specs`); returns an :class:`EpisodeResult` with
        (N, ...) leaves.  This is what lets ``compare_policies`` replay a
        whole suite — fixed baselines, manual, random, Cohmeleon — as a
        single jitted call."""
        cfg = cfg or qlearn.QConfig()
        weights = weights or rewards.PAPER_DEFAULT_WEIGHTS
        n = specs.learned.shape[0]
        if keys is None:
            keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
        kernel = episode_lowering(self.calls, n, self.fused_step)
        cache_key = ("specs_jit", compiled.n_phases, compiled.n_threads,
                     kernel)
        if cache_key not in self._episode_cache:
            ep = self._episode_fn(compiled.n_phases, compiled.n_threads,
                                  kernel)

            def one(sched, spec, cfg_, w, key, f):
                _, res = ep(sched, spec, cfg_, w, key, f)
                return res

            # faults replicate across the policy batch (in_axes None): one
            # FaultSpec perturbs every lowered policy identically.
            self._episode_cache[cache_key] = jax.jit(jax.vmap(
                one, in_axes=(None, 0, None, None, 0, None)))
        return self._episode_cache[cache_key](compiled.schedule, specs,
                                              cfg, weights, keys, faults)

    def baseline_episode(self, compiled: CompiledApp,
                         faults: fault_mod.FaultSpec | None = None
                         ) -> EpisodeResult:
        """Fixed NON_COH_DMA episode — the paper's normalization baseline."""
        _, res = self.episode(compiled, policy="fixed",
                              fixed_modes=CoherenceMode.NON_COH_DMA,
                              faults=faults)
        return res

    # ------------------------------------------------------------ training
    def _train_fn(self, n_phases: int, n_threads: int, eval_shape,
                  kernel: bool | None = None):
        cache_key = (n_phases, n_threads, eval_shape, kernel)
        if cache_key in self._train_cache:
            return self._train_cache[cache_key]
        base_fn = build_train_fn(n_phases, n_threads, eval_shape,
                                 self.cycle_time, self.demand_cache,
                                 presample_noise=self.presample_noise,
                                 ddr_attribution=self.ddr_attribution,
                                 fused=self.fused_step,
                                 debug_finite=self.debug_finite,
                                 kernel=kernel)
        params = self.params

        def train_one(train_scheds, eval_sched, base, cfg, weights, carry,
                      q0, faults=None):
            return base_fn(params, train_scheds, eval_sched, base, None,
                           cfg, weights, carry, q0, faults)

        # Cache the jitted single-agent and vmapped variants so repeated
        # calls (benchmark timing loops, sweeps) hit the jit cache instead
        # of retracing.  ``None`` eval args trace as empty pytrees, so one
        # callable serves both the eval and no-eval protocols (and None
        # faults the no-fault protocol).  Per-agent carry leaves batch
        # (key, best); the iteration counter and the FaultSpec replicate —
        # every agent sees the same fault storm.
        batched = jax.vmap(
            train_one,
            in_axes=(None, None, None, None,
                     rewards.RewardWeights(0, 0, 0),
                     TrainCarry(key=0, it=None, best=0), 0, None),
            out_axes=(0, TrainCarry(key=0, it=None, best=0), 0))
        fns = (jax.jit(train_one), jax.jit(batched))
        self._train_cache[cache_key] = fns
        return fns

    @staticmethod
    def _batched_carry(keys) -> TrainCarry:
        b = keys.shape[0]
        return TrainCarry(key=jnp.asarray(keys),
                          it=jnp.zeros((), jnp.int32),
                          best=jnp.full((b,), -jnp.inf, jnp.float32))

    def train(self, train_apps: Sequence[CompiledApp],
              cfg: qlearn.QConfig,
              weights: rewards.RewardWeights | None = None,
              key=None,
              eval_app: CompiledApp | None = None,
              faults: fault_mod.FaultSpec | None = None
              ) -> tuple[qlearn.QState, tuple]:
        """Train one agent: scan over per-iteration schedules (each compiled
        with its own tile seed, like the DES's per-iteration run seeds)."""
        scheds = stack_schedules(train_apps)
        weights = weights or rewards.PAPER_DEFAULT_WEIGHTS
        key = key if key is not None else jax.random.PRNGKey(0)
        eval_sched = eval_app.schedule if eval_app is not None else None
        base = (self.baseline_episode(eval_app, faults=faults)
                if eval_app is not None else None)
        single, _ = self._train_fn(
            train_apps[0].n_phases, train_apps[0].n_threads,
            None if eval_app is None else
            (eval_app.n_phases, eval_app.n_threads))
        qs, _, hist = single(scheds, eval_sched, base, cfg, weights,
                             init_train_carry(key), qlearn.init_qstate(cfg),
                             faults)
        return qs, hist

    def train_batched(self, train_apps: Sequence[CompiledApp],
                      cfg: qlearn.QConfig,
                      weights_batch: rewards.RewardWeights,
                      keys,
                      eval_app: CompiledApp | None = None,
                      faults: fault_mod.FaultSpec | None = None
                      ) -> tuple[qlearn.QState, tuple]:
        """Train B agents in one call: ``vmap`` over (reward weights, PRNG
        key) pairs.  ``weights_batch`` has (B,) leaves (rewards.stack_weights)
        and ``keys`` is (B, 2).  Returns a batched QState (leaves with
        leading axis B) and, when ``eval_app`` is given, per-iteration
        (norm_time, norm_mem) histories of shape (B, iterations)."""
        scheds = stack_schedules(train_apps)
        eval_sched = eval_app.schedule if eval_app is not None else None
        base = (self.baseline_episode(eval_app, faults=faults)
                if eval_app is not None else None)
        _, batched = self._train_fn(
            train_apps[0].n_phases, train_apps[0].n_threads,
            None if eval_app is None else
            (eval_app.n_phases, eval_app.n_threads),
            episode_lowering(self.calls, keys.shape[0], self.fused_step))
        q0 = qlearn.init_qstate_batch(cfg, keys.shape[0])
        qs, _, hist = batched(scheds, eval_sched, base, cfg, weights_batch,
                              self._batched_carry(keys), q0, faults)
        return qs, hist

    def train_batched_checkpointed(self, train_apps: Sequence[CompiledApp],
                                   cfg: qlearn.QConfig,
                                   weights_batch: rewards.RewardWeights,
                                   keys, manager, *,
                                   ckpt_every: int = 1,
                                   eval_app: CompiledApp | None = None,
                                   faults: fault_mod.FaultSpec | None = None
                                   ) -> tuple[qlearn.QState, tuple]:
        """Crash-resumable :meth:`train_batched`.

        Training runs in host-side chunks of ``ckpt_every`` iterations;
        after each chunk the ``(QState, TrainCarry, history)`` snapshot is
        saved through ``manager`` (a ``checkpoint.CheckpointManager``).  On
        entry, the latest restorable checkpoint (if any) is loaded and
        training continues from that iteration — the scan is sequential and
        the carry crosses chunk boundaries unchanged, so an interrupted +
        resumed run returns final Q-tables (and histories) bitwise-equal
        to an uninterrupted :meth:`train_batched` with the same arguments
        (pinned by ``tests/test_train_checkpoint.py``).

        History arrays are preallocated at the full (B, iterations) shape
        and written chunk by chunk, so checkpoints have a fixed tree
        structure regardless of when they were taken.
        """
        iters = len(train_apps)
        if ckpt_every < 1:
            raise ValueError("ckpt_every must be >= 1")
        scheds = stack_schedules(train_apps)
        eval_sched = eval_app.schedule if eval_app is not None else None
        base = (self.baseline_episode(eval_app, faults=faults)
                if eval_app is not None else None)
        b = keys.shape[0]
        _, batched = self._train_fn(
            train_apps[0].n_phases, train_apps[0].n_threads,
            None if eval_app is None else
            (eval_app.n_phases, eval_app.n_threads),
            episode_lowering(self.calls, b, self.fused_step))
        qs = qlearn.init_qstate_batch(cfg, b)
        carry = self._batched_carry(keys)
        hist_t = jnp.zeros((b, iters), jnp.float32)
        hist_m = jnp.zeros((b, iters), jnp.float32)
        done = 0

        if manager.latest_step() is not None:
            state = manager.restore({
                "qstate": qs, "carry": carry,
                "hist_t": hist_t, "hist_m": hist_m,
                "done": jnp.zeros((), jnp.int32)})
            qs, carry = state["qstate"], state["carry"]
            hist_t, hist_m = state["hist_t"], state["hist_m"]
            done = int(state["done"])

        while done < iters:
            n = min(ckpt_every, iters - done)
            chunk = jax.tree_util.tree_map(
                lambda x: x[done:done + n], scheds)
            qs, carry, (ht, hm) = batched(chunk, eval_sched, base, cfg,
                                          weights_batch, carry, qs, faults)
            hist_t = hist_t.at[:, done:done + n].set(ht)
            hist_m = hist_m.at[:, done:done + n].set(hm)
            done += n
            manager.save(done, {
                "qstate": qs, "carry": carry,
                "hist_t": hist_t, "hist_m": hist_m,
                "done": jnp.asarray(done, jnp.int32)})
        manager.wait()
        return qs, (hist_t, hist_m)

    def evaluate_batched(self, compiled: CompiledApp,
                         qstates: qlearn.QState,
                         cfg: qlearn.QConfig,
                         keys,
                         faults: fault_mod.FaultSpec | None = None
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Frozen-greedy evaluation of B agents on one app in one call;
        returns (norm_time, norm_mem) of shape (B,) vs the NON_COH base
        (itself run under the same ``faults``, so the ratios isolate the
        policy's contribution from the storm's)."""
        base = self.baseline_episode(compiled, faults=faults)
        kernel = episode_lowering(self.calls, keys.shape[0], self.fused_step)
        cache_key = ("batched_eval", compiled.n_phases, compiled.n_threads,
                     kernel)
        if cache_key not in self._train_cache:
            episode = self._episode_fn(compiled.n_phases,
                                       compiled.n_threads, kernel)
            # rewards don't steer a frozen agent; any weights do
            w = rewards.PAPER_DEFAULT_WEIGHTS

            def eval_one(sched, base_, cfg_, qs, key, f):
                spec = learned_policy_spec(qlearn.freeze(qs), sched)
                _, er = episode(sched, spec, cfg_, w, key, f)
                return normalized_metrics(er, base_)

            self._train_cache[cache_key] = jax.jit(jax.vmap(
                eval_one, in_axes=(None, None, None, 0, 0, None)))
        return self._train_cache[cache_key](compiled.schedule, base, cfg,
                                            qstates, keys, faults)


# ===================================================================== serving
class ServeResult(NamedTuple):
    """Per-request traces of one serving chunk ((n_requests,) leaves).

    Every offered request gets a row; shed requests carry ``executed=
    False``, ``-1`` mode/state/action and zeroed timing columns.  Times
    are simulated cycles (multiply by ``cycle_time`` for seconds);
    ``retries`` counts backed-off admission attempts (``faults.
    FAULT_MAX_RETRIES + 1`` marks a shed request)."""

    t_arr: jnp.ndarray      # (n,) f32 arrival time
    tenant: jnp.ndarray     # (n,) i32
    mode: jnp.ndarray       # (n,) i32 (-1 = shed)
    state_idx: jnp.ndarray  # (n,) i32 (-1 = shed)
    action: jnp.ndarray     # (n,) i32 (-1 = shed)
    exec_time: jnp.ndarray  # (n,) f32 cycles
    offchip: jnp.ndarray    # (n,) f32 line accesses
    reward: jnp.ndarray     # (n,) f32
    executed: jnp.ndarray   # (n,) bool — admitted and served
    latency: jnp.ndarray    # (n,) f32 finish - arrival (0 when shed)
    retries: jnp.ndarray    # (n,) f32 admission attempts used
    depth: jnp.ndarray      # (n,) f32 victim queue depth at arrival
    degraded: jnp.ndarray   # (n,) bool — served under forced NON_COH
    start: jnp.ndarray      # (n,) f32 admitted start time
    finish: jnp.ndarray     # (n,) f32 admitted finish time

    @property
    def served(self):
        return jnp.sum(self.executed.astype(jnp.int32))

    @property
    def shed(self):
        return self.t_arr.shape[-1] - self.served

    @property
    def t_end(self):
        return self.t_arr[..., -1]


# (leaf dtype per ServeResult field — preallocating fixed checkpoint trees)
_SERVE_RESULT_DTYPES = (
    jnp.float32, jnp.int32, jnp.int32, jnp.int32, jnp.int32, jnp.float32,
    jnp.float32, jnp.float32, jnp.bool_, jnp.float32, jnp.float32,
    jnp.float32, jnp.bool_, jnp.float32, jnp.float32)


def _zero_serve_results(n_chunks: int, n_requests: int) -> ServeResult:
    return ServeResult(*(jnp.zeros((n_chunks, n_requests), dt)
                         for dt in _SERVE_RESULT_DTYPES))


def build_serve_fn(n_requests: int, queue_cap: int,
                   ddr_attribution: bool = False, fused: bool = True,
                   debug_finite: bool = False):
    """Build the jit-compatible serving-chunk function.

    The returned ``serve(params, sched, spec, cfg, weights, tspec, carry,
    key, t0, faults)`` runs one chunk of ``n_requests`` offered arrivals
    (``traffic.sample_arrivals`` over the compiled schedule's rows)
    through the fused serving step (:func:`repro.kernels.soc_step.ops.
    fused_serve_episode`): bounded per-accelerator admission queues of
    ``queue_cap`` slots, deadline shedding, retry-with-backoff and the
    overload watchdog — semantics in ``kernels.soc_step.ref.serve_step``.

    Like the episodic closures it takes :class:`LaneParams` first so the
    stacked environment can vmap SoC lanes over it.  Every ``tspec``
    (:class:`~repro.soc.traffic.TrafficSpec`) leaf is traced — offered-
    load sweeps reuse the compiled program.  ``carry=None`` starts a
    fresh stream (idle devices, the spec's Q-table); passing the returned
    :class:`~repro.kernels.soc_step.ref.ServeCarry` back in (with ``t0``
    = the previous chunk's last arrival time) continues it bitwise, which
    is what makes serving checkpointable mid-stream.

    Returns ``(carry, qstate, ServeResult)``; the Q-state is rebuilt from
    the carry (table + watchdog-rewound step counter) plus a visits
    replay over the executed rows, mirroring the fused episode's
    ``qlearn.replay_visits`` contract.  MLP specs (``spec.mlp``) serve
    through the same step — their trained weights ride ``carry.wpack``
    (rebuild the agent with ``mlp._replace(wpack=carry.wpack,
    step=carry.step)``); the returned placeholder ``qstate`` stays
    frozen and untouched.  Arrival, noise and fault pre-sampling run
    under the ``cohm_presample`` name scope.
    """
    from repro.kernels.soc_step import ops as soc_step_ops
    from repro.kernels.soc_step.ref import (SERVE_YCOLS, ServeParams,
                                            StepInputs, init_serve_carry)
    f32 = jnp.float32

    def serve(params: LaneParams, sched: Schedule, spec: PolicySpec, cfg,
              weights, tspec: traffic_mod.TrafficSpec, carry, key, t0,
              faults: fault_mod.FaultSpec | None = None, n_real=None):
        pmat, masks, s = params.pmat, params.masks, params.static
        n_accs = pmat.shape[0]
        # Row sampling spans the lane's REAL rows: stacked lanes pad
        # schedules with valid=False tail rows a request must never
        # invoke, so they pass their real length as a traced ``n_real``.
        n_rows = sched.acc_id.shape[0] if n_real is None else n_real
        qs0 = spec.qstate
        mlp = spec.mlp
        with jax.named_scope("cohm_presample"):
            arr = traffic_mod.sample_arrivals(tspec, n_requests, n_rows, t0)
            acc = sched.acc_id[arr.row]

            # Same one-call select-noise protocol as the episodes; faults
            # are pre-sampled against the *request* accelerator stream, so
            # a storm during a load spike composes with admission
            # per-request.
            noise = qlearn.sample_select_noise(key, (n_requests,),
                                               masks.shape[-1])
            frow = {}
            if faults is not None:
                fr = fault_mod.sample_fault_arrays(faults, acc)
                frow = dict(f_exec=fr.exec_scale, f_ddr=fr.ddr_scale,
                            f_llc=fr.llc_extra, f_retry=fr.retry_cycles)
        # thread/fresh/others/valid/eps/alpha are serve-step-owned
        # placeholders (see serve_step): serving concurrency is between
        # accelerators, and the decay schedule evaluates in-carry because
        # the overload watchdog can rewind the counter mid-stream.
        zf = jnp.zeros((n_requests,), f32)
        xs = StepInputs(
            acc_id=acc, footprint=sched.footprint[arr.row],
            tiles=sched.tiles[arr.row],
            thread=jnp.zeros((n_requests,), jnp.int32),
            fresh=jnp.ones((n_requests,), bool),
            others=jnp.zeros((n_requests, n_accs), bool),
            valid=jnp.ones((n_requests,), bool),
            pre_mode=spec.modes[arr.row],
            profile=pmat[acc], avail=masks[acc],
            eps=zf, alpha=zf, u_explore=noise.u_explore,
            g_pick=noise.g_pick, g_tie=noise.g_tie, **frow)
        # MLP specs drive the serve-side decay/freeze off the MERGED agent
        # (the tabular slot is a frozen placeholder); weights ride the
        # carry so chunk chaining and checkpointing work unchanged.
        if mlp is None:
            frozen_eff, step0_eff = qs0.frozen, qs0.step
        else:
            frozen_eff = jnp.where(spec.qfun, mlp.frozen, qs0.frozen)
            step0_eff = jnp.where(spec.qfun, mlp.step, qs0.step)
        sp = ServeParams(
            eps0=jnp.asarray(cfg.epsilon0, f32),
            alpha0=jnp.asarray(cfg.alpha0, f32),
            decay_steps=jnp.asarray(cfg.decay_steps, f32),
            reopen_frac=jnp.asarray(cfg.reopen_frac, f32),
            frozen=frozen_eff.astype(f32),
            backoff=tspec.backoff,
            overload_frac=tspec.overload_frac,
            pressure_beta=tspec.pressure_beta,
            prio_reserve=tspec.prio_reserve)
        if carry is None:
            carry = init_serve_carry(
                qs0.qtable, rewards.init_reward_state(n_accs).extrema,
                n_accs, sched.tiles.shape[-1], queue_cap, step0_eff,
                wpack0=None if mlp is None else mlp.wpack)
        carry, ys = soc_step_ops.fused_serve_episode(
            s, spec.learned, weights, sp, carry, xs, arr.t_arr,
            arr.deadline, arr.priority, ddr_attribution=ddr_attribution,
            kernel=None if fused else False,
            qfun=None if mlp is None else spec.qfun, mlp=mlp)

        cols = {name: ys[:, i] for i, name in enumerate(SERVE_YCOLS)}
        executed = cols["executed"] > 0.0
        # Visits/step replay (the fused-episode contract): shed rows have
        # -1 indices but zero increments — clamp and scatter-add nothing.
        inc = (executed & ~qs0.frozen).astype(jnp.int32)
        sidx = jnp.maximum(cols["state_idx"].astype(jnp.int32), 0)
        act = jnp.maximum(cols["action"].astype(jnp.int32), 0)
        qs = qlearn.QState(qtable=carry.qtable,
                           visits=qs0.visits.at[sidx, act].add(inc),
                           step=carry.step, frozen=qs0.frozen)
        if debug_finite:
            qlearn.debug_finite_check("vecenv.serve",
                                      reward=cols["reward"],
                                      qtable=qs.qtable)
        res = ServeResult(
            t_arr=arr.t_arr, tenant=arr.tenant,
            mode=cols["mode"].astype(jnp.int32),
            state_idx=cols["state_idx"].astype(jnp.int32),
            action=cols["action"].astype(jnp.int32),
            exec_time=cols["exec_time"], offchip=cols["offchip"],
            reward=cols["reward"], executed=executed,
            latency=cols["latency"], retries=cols["retries"],
            depth=cols["depth"], degraded=cols["degraded"] > 0.0,
            start=cols["start"], finish=cols["finish"])
        return carry, qs, res

    return serve


class ServeEnv:
    """Long-lived continuous-traffic serving over a :class:`VecEnv`.

    Where :meth:`VecEnv.episode` replays a closed invocation schedule,
    ``ServeEnv`` keeps the SoC *always on*: requests arrive over
    continuous time from a :class:`~repro.soc.traffic.TrafficSpec`, are
    admitted to bounded per-accelerator queues (``queue_cap`` static ring
    slots in the scan carry), shed when their deadline cannot be met
    (after bounded exponential retry-with-backoff), and — under sustained
    queue-full pressure — served in forced NON_COH mode while the
    epsilon-reopen watchdog un-freezes exploration so the agent re-adapts
    instead of letting latency diverge.

    ``traffic=None`` calls delegate verbatim to the episodic path, so a
    traffic-free ``serve`` is bitwise-identical to :meth:`VecEnv.
    episode_spec` (pinned by ``tests/test_soc_traffic.py``).  Chunks
    chain: ``serve`` returns a ``ServeCarry`` + the final arrival clock,
    and feeding them back continues the stream bitwise —
    :meth:`serve_checkpointed` uses that to make multi-chunk serving
    crash-resumable through a ``checkpoint.CheckpointManager``.
    """

    def __init__(self, env: VecEnv, *, queue_cap: int = 8,
                 n_requests: int = 1024):
        if queue_cap < 1:
            raise ValueError("queue_cap must be >= 1")
        self.env = env
        self.queue_cap = int(queue_cap)
        self.n_requests = int(n_requests)
        self._serve_cache: dict = {}

    # ------------------------------------------------------------- plumbing
    def _serve_fn(self, n_requests: int):
        cache_key = ("serve", n_requests)
        if cache_key in self._serve_cache:
            return self._serve_cache[cache_key]
        env = self.env
        base = build_serve_fn(n_requests, self.queue_cap,
                              ddr_attribution=env.ddr_attribution,
                              fused=env.fused_step,
                              debug_finite=env.debug_finite)
        params = env.params

        def serve(sched, spec, cfg, weights, tspec, carry, key, t0,
                  faults=None):
            return base(params, sched, spec, cfg, weights, tspec, carry,
                        key, t0, faults)

        fns = (jax.jit(serve),
               # Policy batches: specs/keys carry a leading (N,) axis;
               # traffic, carry(None) and faults replicate — every
               # lowered policy faces the identical offered stream.
               jax.jit(jax.vmap(
                   serve,
                   in_axes=(None, 0, None, None, None, None, 0, None,
                            None))))
        self._serve_cache[cache_key] = fns
        return fns

    def init_carry(self, qstate: qlearn.QState, mlp=None, qfun=None):
        """A fresh stream state (idle devices, the agent's Q-table).

        For an MLP-lowered spec pass ``(spec.qstate, spec.mlp,
        spec.qfun)`` — the weight pack joins the carry and the decay
        counter starts at the merged agent's step."""
        from repro.kernels.soc_step.ref import init_serve_carry
        n_accs = self.env.pmat.shape[0]
        step0 = (qstate.step if mlp is None
                 else jnp.where(qfun, mlp.step, qstate.step))
        return init_serve_carry(
            qstate.qtable, rewards.init_reward_state(n_accs).extrema,
            n_accs, self.env.soc.n_mem_tiles, self.queue_cap, step0,
            wpack0=None if mlp is None else mlp.wpack)

    # --------------------------------------------------------------- serving
    def serve(self, compiled: CompiledApp, spec: PolicySpec,
              traffic: traffic_mod.TrafficSpec | None = None, *,
              cfg: qlearn.QConfig | None = None,
              weights: rewards.RewardWeights | None = None,
              key=None, carry=None, t0=0.0,
              n_requests: int | None = None,
              faults: fault_mod.FaultSpec | None = None):
        """Serve one chunk of offered traffic with a lowered policy.

        Returns ``(carry, qstate, ServeResult)``.  With ``traffic=None``
        this *is* :meth:`VecEnv.episode_spec` (returning its ``(qstate,
        EpisodeResult)``) — the episodic path, bitwise."""
        if traffic is None:
            return self.env.episode_spec(compiled, spec, cfg=cfg,
                                         weights=weights, key=key,
                                         faults=faults)
        with jax.profiler.TraceAnnotation("cohm.prep"):
            cfg = cfg or qlearn.QConfig()
            weights = weights or rewards.PAPER_DEFAULT_WEIGHTS
            key = key if key is not None else jax.random.PRNGKey(0)
            t0 = jnp.asarray(t0, jnp.float32)
        with jax.profiler.TraceAnnotation("cohm.launch"):
            fn, _ = self._serve_fn(int(n_requests or self.n_requests))
            return fn(compiled.schedule, spec, cfg, weights, traffic, carry,
                      key, t0, faults)

    def serve_specs(self, compiled: CompiledApp, specs: PolicySpec,
                    traffic: traffic_mod.TrafficSpec, *,
                    cfg: qlearn.QConfig | None = None,
                    weights: rewards.RewardWeights | None = None,
                    keys=None, n_requests: int | None = None,
                    faults: fault_mod.FaultSpec | None = None):
        """A heterogeneous batch of lowered policies against one offered
        stream, one call — the serving analogue of :meth:`VecEnv.
        episodes` (Q vs fixed under identical arrivals).  Returns
        ``(carry, qstate, ServeResult)`` with (N, ...) leaves."""
        cfg = cfg or qlearn.QConfig()
        weights = weights or rewards.PAPER_DEFAULT_WEIGHTS
        n = specs.learned.shape[0]
        if keys is None:
            keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
        _, batched = self._serve_fn(int(n_requests or self.n_requests))
        return batched(compiled.schedule, specs, cfg, weights, traffic,
                       None, keys, jnp.zeros((), jnp.float32), faults)

    def serve_checkpointed(self, compiled: CompiledApp, spec: PolicySpec,
                           traffic: traffic_mod.TrafficSpec, manager, *,
                           n_chunks: int,
                           cfg: qlearn.QConfig | None = None,
                           weights: rewards.RewardWeights | None = None,
                           key=None, n_requests: int | None = None,
                           faults: fault_mod.FaultSpec | None = None):
        """Crash-resumable multi-chunk serving (the ``train_batched_
        checkpointed`` pattern on an open stream).

        Chunk ``i`` draws arrivals from ``traffic.key`` fold_in ``i``
        (:func:`repro.soc.traffic.chunk_key`) and select noise from
        ``key`` fold_in ``i``; the ``ServeCarry`` and arrival clock cross
        chunk boundaries unchanged, so an interrupted + resumed run
        returns a final ``(carry, qstate, ServeResult)`` bitwise-equal to
        an uninterrupted one with the same arguments (pinned by
        ``tests/test_soc_traffic.py``).  Result arrays are preallocated
        at the full ``(n_chunks, n_requests)`` shape so checkpoints have
        a fixed tree structure; the returned :class:`ServeResult` leaves
        are flattened to ``(n_chunks * n_requests,)`` request order."""
        if n_chunks < 1:
            raise ValueError("n_chunks must be >= 1")
        cfg = cfg or qlearn.QConfig()
        weights = weights or rewards.PAPER_DEFAULT_WEIGHTS
        key = key if key is not None else jax.random.PRNGKey(0)
        n = int(n_requests or self.n_requests)
        fn, _ = self._serve_fn(n)

        carry = self.init_carry(spec.qstate, spec.mlp, spec.qfun)
        qs = spec.qstate
        results = _zero_serve_results(n_chunks, n)
        t0 = jnp.zeros((), jnp.float32)
        done = 0
        if manager.latest_step() is not None:
            state = manager.restore({
                "carry": carry, "qstate": qs, "results": results,
                "t0": t0, "done": jnp.zeros((), jnp.int32)})
            carry, qs = state["carry"], state["qstate"]
            results, t0 = state["results"], state["t0"]
            done = int(state["done"])

        while done < n_chunks:
            carry, qs, res = fn(
                compiled.schedule, spec._replace(qstate=qs), cfg, weights,
                traffic_mod.chunk_key(traffic, done), carry,
                jax.random.fold_in(key, done), t0, faults)
            results = jax.tree_util.tree_map(
                lambda acc_, r: acc_.at[done].set(r), results, res)
            t0 = res.t_arr[-1]
            done += 1
            manager.save(done, {
                "carry": carry, "qstate": qs, "results": results,
                "t0": t0, "done": jnp.asarray(done, jnp.int32)})
        manager.wait()
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), results)
        return carry, qs, flat
