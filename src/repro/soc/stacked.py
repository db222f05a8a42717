"""Third vmap axis over SoC configurations (paper Fig. 9 in one call).

``soc.vecenv`` batches agents (reward weights x seeds) over one SoC;
this module pads K heterogeneous SoCs — different accelerator counts,
memory-tile counts, thread widths, schedule lengths, phase counts — to a
common shape and ``vmap``s the same episode/training closures over a
leading *lane* axis:

  * :func:`compile_apps_stacked` compiles one application per SoC (the
    DES's rng protocol per lane, so per-lane results are unchanged) and
    pads schedules to a common ``(S_max, T_max, tiles_max)``; padding rows
    carry ``valid=False`` and sit at the tail of each lane, so they leave
    the Q-table, reward extrema and slot table untouched (the ``gated``
    episode variant) and consume no real PRNG stream;
  * :class:`StackedVecEnv` stacks per-SoC :class:`~repro.soc.vecenv.
    LaneParams` (profile matrices, action masks, timing scalars) along
    axis 0 and exposes ONE batched episode entry point —
    :meth:`StackedVecEnv.episodes` over a ``(K lanes, N policies)`` batch
    of lowered :class:`~repro.soc.vecenv.PolicySpec`s, heterogeneous
    families welcome — plus ``train_batched`` over (SoC lanes x agents).
    Fig. 9's eight SoCs train in one call and evaluate EVERY policy
    family (fixed suite, manual, random, Cohmeleon) in one more;
  * :func:`length_buckets` / :func:`compile_apps_bucketed` optionally
    split lanes by schedule length (greedy k-way cuts on the sorted
    prefix-waste curve): when lengths diverge, a few tight stacked calls
    beat one call padded to the global max (~15% padded-step waste on
    the Fig. 9 set with two buckets; measured in
    ``benchmarks/vecenv_throughput.py``), and :func:`reassemble_lanes`
    scatters per-bucket results back to original lane order — the
    design-space sweep (:mod:`repro.soc.dse`) runs hundreds of generated
    SoCs this way.

Per-lane equivalence: a lane of a stacked call reproduces the same
episode the lane's own :class:`VecEnv` runs (padded slots/tiles are
masked everywhere), which in turn matches the DES on single-thread
applications — pinned by ``tests/test_vecenv_stacked.py``.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qlearn, rewards
from repro.core.modes import CoherenceMode
from repro.core.policies import FixedHomogeneous, Policy
from repro.soc import vecenv as vec
from repro.soc.config import SoCConfig
from repro.soc.des import Application, SoCSimulator
from repro.soc.memsys import SoCStatic


@dataclasses.dataclass(frozen=True)
class StackedApps:
    """K compiled applications padded to a common schedule shape.

    ``schedule`` leaves carry a leading lane axis ``(K, S_max, ...)``;
    ``phase_mask[k, p]`` marks lane ``k``'s real phases and feeds the
    masked per-phase normalization."""

    schedule: vec.Schedule
    n_phases: int                  # padded P_max
    n_threads: int                 # padded T_max
    n_tiles: int                   # padded memory-tile axis
    n_steps: tuple                 # (K,) real invocations per lane
    phase_mask: jnp.ndarray        # (K, P_max) bool
    names: tuple
    phase_names: tuple             # per lane, real phases only
    compiled: tuple                # per-lane unpadded CompiledApp

    @property
    def n_lanes(self) -> int:
        return len(self.compiled)


def _pad_axis(arr: np.ndarray, axis: int, target: int, fill):
    if arr.shape[axis] == target:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, target - arr.shape[axis])
    return np.pad(arr, widths, constant_values=fill)


def pad_compiled(c: vec.CompiledApp, n_steps: int, n_threads: int,
                 n_tiles: int) -> vec.Schedule:
    """Pad one compiled schedule to ``(n_steps, n_threads, n_tiles)``.

    Padding rows are ``valid=False`` no-ops at the tail; padded thread
    slots / memory tiles are never set in any mask, so they contribute
    zeros to every sensed or timed quantity."""
    s = jax.tree_util.tree_map(np.asarray, c.schedule)
    return vec.Schedule(
        acc_id=_pad_axis(s.acc_id, 0, n_steps, 0),
        footprint=_pad_axis(s.footprint, 0, n_steps, 1.0),
        tiles=_pad_axis(_pad_axis(s.tiles, 1, n_tiles, False),
                        0, n_steps, False),
        thread=_pad_axis(s.thread, 0, n_steps, 0),
        phase_id=_pad_axis(s.phase_id, 0, n_steps, 0),
        fresh=_pad_axis(s.fresh, 0, n_steps, True),
        others=_pad_axis(_pad_axis(s.others, 1, n_threads, False),
                         0, n_steps, False),
        valid=_pad_axis(s.valid, 0, n_steps, False),
    )


def _stack_compiled(compiled: Sequence[vec.CompiledApp],
                    socs: Sequence[SoCConfig]) -> StackedApps:
    """Pad pre-compiled lanes to a common shape and stack them."""
    n_steps = max(c.n_steps for c in compiled)
    n_threads = max(c.n_threads for c in compiled)
    n_tiles = max(soc.n_mem_tiles for soc in socs)
    n_phases = max(c.n_phases for c in compiled)
    padded = [pad_compiled(c, n_steps, n_threads, n_tiles) for c in compiled]
    schedule = jax.tree_util.tree_map(
        lambda *xs: jnp.asarray(np.stack(xs)), *padded)
    phase_mask = jnp.asarray(np.stack([
        np.arange(n_phases) < c.n_phases for c in compiled]))
    return StackedApps(
        schedule=schedule, n_phases=n_phases, n_threads=n_threads,
        n_tiles=n_tiles, n_steps=tuple(c.n_steps for c in compiled),
        phase_mask=phase_mask, names=tuple(c.name for c in compiled),
        phase_names=tuple(c.phase_names for c in compiled),
        compiled=tuple(compiled))


def _compile_lanes(apps, socs, seed) -> list[vec.CompiledApp]:
    if len(apps) != len(socs):
        raise ValueError(f"{len(apps)} apps vs {len(socs)} socs")
    if np.isscalar(seed):
        seeds = [seed] * len(apps)
    else:
        seeds = list(seed)
        if len(seeds) != len(apps):
            raise ValueError(
                f"{len(seeds)} per-lane seeds vs {len(apps)} apps — "
                "a seed sequence must give exactly one seed per lane")
    return [vec.compile_app(a, soc, seed=s)
            for a, soc, s in zip(apps, socs, seeds)]


def compile_apps_stacked(apps: Sequence[Application],
                         socs: Sequence[SoCConfig],
                         seed: int | Sequence[int] = 0) -> StackedApps:
    """Compile one application per SoC and stack to a common shape.

    ``seed`` follows :func:`~repro.soc.vecenv.compile_app`'s tile-striping
    protocol — a scalar is shared by every lane (each lane still draws its
    own rng stream, exactly as its unstacked compile would), a sequence
    gives one seed per lane."""
    return _stack_compiled(_compile_lanes(apps, socs, seed), list(socs))


def padded_waste(stacked: StackedApps) -> float:
    """Fraction of the stacked scan's steps that are padding no-ops."""
    k, s_max = stacked.schedule.acc_id.shape[:2]
    return 1.0 - sum(stacked.n_steps) / float(k * s_max)


def length_buckets(lengths: Sequence[int], max_buckets: int = 2,
                   min_gain: float = 0.05) -> list[list[int]]:
    """Partition lane indices by schedule length to cut padded-step waste.

    Every lane of a stacked call pads to the longest schedule in its
    bucket; when lengths diverge, splitting the lanes into up to
    ``max_buckets`` calls — each padded only to its own max — trades
    extra dispatches for fewer wasted scan steps (~15% on the Fig. 9 set
    with 2 buckets; much more on generated design-space samples).

    Cuts are placed greedily on the sorted-length prefix-waste curve:
    each round takes the single cut (anywhere inside any current bucket)
    that removes the most padded volume, and stops when the best cut
    saves less than ``min_gain`` of the single-call scan volume
    (``k * max(lengths)``) — so near-uniform sets still return one
    bucket, and ``max_buckets=2`` reproduces the old single-cut search
    exactly.  Returns index groups in ascending length order, original
    index order inside each group."""
    lens = [int(l) for l in lengths]
    k = len(lens)
    single = [list(range(k))]
    if k < 2 or max_buckets < 2:
        return single
    order = sorted(range(k), key=lambda i: lens[i])
    sl = [lens[i] for i in order]
    volume = float(k * sl[-1])

    def seg_waste(a: int, b: int) -> int:
        """Padded waste of sorted segment [a, b) stacked as one call."""
        return sl[b - 1] * (b - a) - sum(sl[a:b])

    cuts = [0, k]
    while len(cuts) - 1 < max_buckets:
        best_gain, best_cut = 0.0, None
        for a, b in zip(cuts, cuts[1:]):
            base = seg_waste(a, b)
            for c in range(a + 1, b):
                gain = (base - seg_waste(a, c) - seg_waste(c, b)) / volume
                if gain > best_gain:
                    best_gain, best_cut = gain, c
        if best_cut is None or best_gain < min_gain:
            break
        cuts = sorted(cuts + [best_cut])
    if len(cuts) == 2:
        return single
    return [sorted(order[a:b]) for a, b in zip(cuts, cuts[1:])]


def compile_apps_bucketed(
    apps: Sequence[Application], socs: Sequence[SoCConfig],
    seed: int | Sequence[int] = 0, max_buckets: int = 2,
    min_gain: float = 0.05,
) -> list[tuple[list[int], StackedApps]]:
    """:func:`compile_apps_stacked` with length bucketing: returns one
    ``(lane_indices, StackedApps)`` per bucket (at most ``max_buckets``).
    Pair each bucket with :meth:`StackedVecEnv.sublanes` to run it and
    :func:`reassemble_lanes` to put per-bucket results back in lane
    order."""
    compiled = _compile_lanes(apps, socs, seed)
    groups = length_buckets([c.n_steps for c in compiled],
                            max_buckets=max_buckets, min_gain=min_gain)
    return [(g, _stack_compiled([compiled[i] for i in g],
                                [socs[i] for i in g]))
            for g in groups]


def reassemble_lanes(groups: Sequence[Sequence[int]], parts: Sequence):
    """Invert bucketing: scatter per-bucket results back to lane order.

    ``groups`` are the index groups of :func:`length_buckets` /
    :func:`compile_apps_bucketed` (they partition ``range(k)``) and
    ``parts`` one pytree per bucket whose leaves carry that bucket's
    lanes on the leading axis.  Leaves must share trailing shapes across
    buckets — reduce per-lane metrics (e.g. normalized scalars) before
    reassembling, since buckets pad phases/steps to different maxima.
    Returns one pytree with leading axis ``k`` in original lane order."""
    index = np.concatenate([np.asarray(list(g), int) for g in groups])
    if sorted(index.tolist()) != list(range(len(index))):
        raise ValueError(f"groups {list(map(list, groups))} do not "
                         "partition the lane range")
    inv = np.argsort(index, kind="stable")

    def scatter(*leaves):
        return np.concatenate([np.asarray(l) for l in leaves])[inv]

    return jax.tree_util.tree_map(scatter, *parts)


@dataclasses.dataclass(frozen=True)
class _LaneView:
    """One stacked lane behind the vecenv lowering protocol (``.params``
    padded to the stacked shape, ``.profiles`` the lane's real ones)."""

    params: vec.LaneParams
    profiles: list


@dataclasses.dataclass(frozen=True)
class _LaneSchedule:
    """A padded lane schedule behind the ``.schedule`` protocol."""

    schedule: vec.Schedule


def _cfg_axes(cfg: qlearn.QConfig):
    """vmap in_axes spec for a QConfig whose leaves may carry a lane axis."""
    return qlearn.QConfig(*[
        0 if (hasattr(v, "ndim") and getattr(v, "ndim", 0) >= 1) else None
        for v in cfg])


# Rows of a flattened episode batch that fill whole lane tiles of a TPU
# vector register: only such a batch ran the step scan faster flat than
# under nested vmaps on a v5e (PERF.md section 6).
FLAT_GRID_ROWS = 128


def _vmap_grid(fn, lane_axes, item_axes, out_axes):
    """``vmap(vmap(fn, item_axes), lane_axes)``, as ONE ``vmap`` over the
    flattened (K lanes x N items) grid where K*N fills whole
    :data:`FLAT_GRID_ROWS` tiles.

    ``lane_axes`` and ``item_axes`` are ``vmap`` in_axes prefixes of 0 or
    None; ``out_axes`` (0 or None) holds for both levels.  Flat, a leaf
    batched over only one of the two axes is broadcast over the other,
    every batched leaf is flattened to a leading K*N axis and batched
    outputs come back as (K, N, ...).  One level also batches a scan in
    ``fn`` once, where nested vmaps batch it again at the outer level,
    over a body already batched."""
    nested = jax.vmap(jax.vmap(fn, in_axes=item_axes, out_axes=out_axes),
                      in_axes=lane_axes, out_axes=out_axes)

    def marked(axes, tree):
        # None axes as -1, so broadcasting the prefix keeps them as leaves.
        axes = jax.tree_util.tree_map(lambda a: -1 if a is None else a,
                                      axes, is_leaf=lambda a: a is None)
        return jax.tree.broadcast(axes, tree)

    def run(*args):
        lane, item = marked(lane_axes, args), marked(item_axes, args)
        leaves = list(zip(*(jax.tree_util.tree_leaves(t)
                            for t in (args, lane, item))))
        k = next(x.shape[0] for x, l, _ in leaves if l == 0)
        n = next(x.shape[l + 1] for x, l, i in leaves if i == 0)
        if (k * n) % FLAT_GRID_ROWS:
            return nested(*args)

        def flat(x, l, i):
            if l == i == -1:
                return x
            if l == -1:
                x = jnp.broadcast_to(x, (k,) + x.shape)
            elif i == -1:
                x = jnp.broadcast_to(x[:, None], (k, n) + x.shape[1:])
            return x.reshape((k * n,) + x.shape[2:])

        out = jax.vmap(
            fn, in_axes=jax.tree.map(lambda l, i: None if l == i == -1
                                     else 0, lane, item),
            out_axes=out_axes)(*jax.tree.map(flat, args, lane, item))
        return jax.tree.map(
            lambda x, o: x if o == -1 else x.reshape((k, n) + x.shape[1:]),
            out, marked(out_axes, out))

    return run


class StackedVecEnv:
    """K SoCs as one vmapped environment (always the carry-cached step).

    Build with :meth:`from_simulators` to share DES simulators' resolved
    accelerator profiles (the cross-backend comparison protocol), or
    directly from configs.  All public entry points run every lane in a
    single jitted call; each one's per-call host preparation runs under a
    ``cohm.prep`` profiler span, and its jit cache lookup and call under
    ``cohm.launch``.

    ``fused_step`` follows :class:`~repro.soc.vecenv.VecEnv`: ``None``
    (default) enables the :mod:`repro.kernels.soc_step` episode lowering —
    the stacked path always runs the fast (demand-cached, presampled)
    step, so only equivalence tests pass ``False``.  Training and episode
    calls lower that step by how many episodes they run, lanes times
    agents or policies (:func:`~repro.soc.vecenv.episode_lowering`).
    """

    def __init__(self, socs: Sequence[SoCConfig], seed: int = 0,
                 flavors: Sequence[str] | str = "mixed",
                 envs: Sequence[vec.VecEnv] | None = None,
                 cycle_time: float = 1e-8,
                 fused_step: bool | None = None):
        if envs is None:
            if isinstance(flavors, str):
                flavors = [flavors] * len(socs)
            envs = [vec.VecEnv(soc, seed=seed, flavor=fl,
                               cycle_time=cycle_time)
                    for soc, fl in zip(socs, flavors)]
        self.envs = list(envs)
        self.socs = [e.soc for e in self.envs]
        self.cycle_time = float(self.envs[0].cycle_time)
        n_accs = max(soc.n_accs for soc in self.socs)
        feat = self.envs[0].pmat.shape[1]
        pmat = np.zeros((len(self.envs), n_accs, feat), np.float32)
        masks = np.ones((len(self.envs), n_accs, self.envs[0].masks.shape[1]),
                        bool)
        for k, env in enumerate(self.envs):
            pmat[k, :env.soc.n_accs] = np.asarray(env.pmat)
            masks[k, :env.soc.n_accs] = np.asarray(env.masks)
        static = SoCStatic(*[
            jnp.asarray([getattr(env.static, f) for env in self.envs],
                        jnp.float32)
            for f in SoCStatic._fields])
        self.n_accs = n_accs
        self.fused_step = bool(True if fused_step is None else fused_step)
        self.params = vec.LaneParams(pmat=jnp.asarray(pmat),
                                     masks=jnp.asarray(masks),
                                     static=static)
        self._cache: dict = {}
        # Jitted-call accounting: fig9's acceptance protocol asserts the
        # whole figure is one train + one eval call in --quick mode; each
        # train or episodes call also counts its step lowering
        # (``episode_kernel`` / ``episode_scan``).
        self.calls = collections.Counter()

    @classmethod
    def from_simulators(cls, sims: Sequence[SoCSimulator],
                        cycle_time: float = 1e-8) -> "StackedVecEnv":
        envs = [vec.VecEnv.from_simulator(sim, cycle_time=cycle_time)
                for sim in sims]
        return cls([s.soc for s in sims], envs=envs, cycle_time=cycle_time)

    @property
    def n_lanes(self) -> int:
        return len(self.envs)

    def sublanes(self, lanes: Sequence[int]) -> "StackedVecEnv":
        """A stacked environment over a lane subset (shares the per-lane
        VecEnvs) — the execution half of :func:`length_buckets`."""
        return StackedVecEnv([self.socs[i] for i in lanes],
                             envs=[self.envs[i] for i in lanes],
                             cycle_time=self.cycle_time,
                             fused_step=self.fused_step)

    def compile(self, apps: Sequence[Application],
                seed: int | Sequence[int] = 0) -> StackedApps:
        return compile_apps_stacked(apps, self.socs, seed)

    # ------------------------------------------------------------ episodes
    def _episode_fn(self, n_phases: int, n_threads: int,
                    kernel: bool | None):
        key = ("ep", n_phases, n_threads, kernel)
        if key not in self._cache:
            self._cache[key] = vec.build_episode_fn(
                n_phases, n_threads, self.cycle_time,
                demand_cache=True, gated=True, fused=self.fused_step,
                kernel=kernel)
        return self._cache[key]

    def _default_keys(self, *batch) -> jnp.ndarray:
        n = int(np.prod(batch))
        return jax.vmap(jax.random.PRNGKey)(jnp.arange(n)).reshape(
            *batch, 2)

    def lane_view(self, lane: int):
        """Lane ``lane`` as a vecenv-protocol object (``.params`` padded to
        the stacked shape, ``.profiles``) — what ``Policy.lower`` needs."""
        return _LaneView(
            params=jax.tree_util.tree_map(lambda x: x[lane], self.params),
            profiles=self.envs[lane].profiles)

    def lower(self, stacked: StackedApps,
              policies) -> vec.PolicySpec:
        """Lower policies onto every padded lane: ``(K, N, ...)`` specs.

        ``policies`` is either one sequence of N :class:`Policy` shared by
        all lanes, or K sequences (N each) for per-lane assignments (e.g.
        per-SoC profiled heterogeneous baselines, per-SoC trained agents).
        The result feeds :meth:`episodes` directly."""
        if policies and isinstance(policies[0], Policy):
            policies = [policies] * self.n_lanes
        if len(policies) != self.n_lanes:
            raise ValueError(
                f"{len(policies)} policy rows vs {self.n_lanes} lanes")
        lane_specs = []
        for k, pols in enumerate(policies):
            view = self.lane_view(k)
            lane = _LaneSchedule(schedule=jax.tree_util.tree_map(
                lambda x: x[k], stacked.schedule))
            lane_specs.append(vec.stack_specs(
                [pol.lower(view, lane) for pol in pols]))
        return jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *lane_specs)

    def lower_qstates(self, stacked: StackedApps, qstates: qlearn.QState,
                      freeze: bool = True) -> vec.PolicySpec:
        """Lower a (K, B) batch of trained agents into learned specs
        ((K, B, ...) leaves; ``freeze=True`` is the evaluation protocol)."""
        if freeze:
            # per-agent frozen flags (scalar-freeze would break the vmap)
            qstates = qstates._replace(
                frozen=jnp.ones(qstates.qtable.shape[:2], bool))
        k, b = qstates.qtable.shape[:2]
        s = stacked.schedule.acc_id.shape[-1]
        return vec.PolicySpec(
            modes=jnp.zeros((k, b, s), jnp.int32),
            learned=jnp.ones((k, b), bool),
            qstate=qstates)

    def lower_mlps(self, stacked: StackedApps, mlps,
                   freeze: bool = True) -> vec.PolicySpec:
        """Lower a (K, B) batch of function-approximation agents
        (:class:`repro.soc.nn.MLPQState` with (K, B)-leading leaves) into
        qfun specs ((K, B, ...) leaves) — the MLP analogue of
        :meth:`lower_qstates`.  The tabular slot broadcasts one frozen
        placeholder per (lane, agent)."""
        k, b = mlps.wpack.shape[:2]
        if freeze:
            mlps = mlps._replace(frozen=jnp.ones((k, b), bool))
        s = stacked.schedule.acc_id.shape[-1]
        qstate = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (k, b) + x.shape),
            qlearn.frozen_qstate())
        return vec.PolicySpec(
            modes=jnp.zeros((k, b, s), jnp.int32),
            learned=jnp.zeros((k, b), bool),
            qstate=qstate,
            qfun=jnp.ones((k, b), bool),
            mlp=mlps)

    def episodes(self, stacked: StackedApps, specs: vec.PolicySpec,
                 cfg: qlearn.QConfig | None = None,
                 keys=None, faults=None) -> vec.EpisodeResult:
        """Every (lane, policy) episode of a heterogeneous spec batch in
        ONE jitted call.

        ``specs`` leaves carry a leading ``(K, N)`` (lanes x policies)
        batch — mixed families welcome (:meth:`lower` builds them from
        Policy objects, :meth:`lower_qstates` from trained agents) —
        and the returned EpisodeResult has (K, N, ...) leaves.  This
        replaces the old per-family ``episodes_fixed`` /
        ``episodes_manual`` / ``episodes_q`` triple: the Fig. 9
        evaluation is one call for ALL families across ALL SoCs."""
        self.calls["episodes"] += 1
        with jax.profiler.TraceAnnotation("cohm.prep"):
            cfg = cfg or qlearn.QConfig()
            K, N = specs.learned.shape
            if keys is None:
                keys = self._default_keys(K, N)
            axes = _cfg_axes(cfg)
            kernel = vec.episode_lowering(self.calls, K * N, self.fused_step)
        with jax.profiler.TraceAnnotation("cohm.launch"):
            cache_key = ("episodes_jit", stacked.n_phases,
                         stacked.n_threads, tuple(axes), kernel)
            if cache_key not in self._cache:
                ep = self._episode_fn(stacked.n_phases, stacked.n_threads,
                                      kernel)
                w = rewards.PAPER_DEFAULT_WEIGHTS

                # One FaultSpec perturbs every (lane, policy) episode
                # identically: in_axes None at both vmap levels.
                def one(params, sched, cfg_, spec, key, f):
                    _, res = ep(params, sched, spec, cfg_, w, key, f)
                    return res

                self._cache[cache_key] = jax.jit(_vmap_grid(
                    one, lane_axes=(0, 0, axes, 0, 0, None),
                    item_axes=(None, None, None, 0, 0, None),
                    out_axes=0))
            return self._cache[cache_key](self.params, stacked.schedule,
                                          cfg, specs, keys, faults)

    def baseline(self, stacked: StackedApps,
                 faults=None) -> vec.EpisodeResult:
        """Per-lane fixed NON_COH_DMA episode ((K, ...) leaves) — the
        paper's normalization baseline."""
        specs = self.lower(stacked,
                           [FixedHomogeneous(CoherenceMode.NON_COH_DMA)])
        res = self.episodes(stacked, specs, faults=faults)
        return jax.tree_util.tree_map(lambda x: x[:, 0], res)

    # ------------------------------------------------------------- serving
    def serve(self, stacked: StackedApps, specs: vec.PolicySpec,
              traffic, cfg: qlearn.QConfig | None = None,
              keys=None, faults=None, *, queue_cap: int = 8,
              n_requests: int = 1024):
        """Every (lane, policy) serving chunk of one offered stream in ONE
        jitted call — the serving analogue of :meth:`episodes`.

        ``specs`` leaves carry a leading ``(K, N)`` batch; the
        :class:`~repro.soc.traffic.TrafficSpec` replicates across lanes
        and policies (identical arrival times/tenants everywhere — lanes
        map the shared row *indices* onto their own schedules, sampled
        over each lane's real row count so padding rows are never
        invoked).  Returns ``(carry, qstate, ServeResult)`` with
        ``(K, N, ...)`` leaves."""
        self.calls["serve"] += 1
        with jax.profiler.TraceAnnotation("cohm.prep"):
            cfg = cfg or qlearn.QConfig()
            K, N = specs.learned.shape
            if keys is None:
                keys = self._default_keys(K, N)
            axes = _cfg_axes(cfg)
            n_real = jnp.asarray(stacked.n_steps, jnp.int32)
        with jax.profiler.TraceAnnotation("cohm.launch"):
            cache_key = ("serve_jit", stacked.n_phases, stacked.n_threads,
                         queue_cap, n_requests, tuple(axes))
            if cache_key not in self._cache:
                base = vec.build_serve_fn(n_requests, queue_cap,
                                          fused=self.fused_step)
                w = rewards.PAPER_DEFAULT_WEIGHTS
                t0 = jnp.zeros((), jnp.float32)

                def one(params, sched, n_real, cfg_, spec, tspec, key, f):
                    return base(params, sched, spec, cfg_, w, tspec, None,
                                key, t0, f, n_real)

                self._cache[cache_key] = jax.jit(jax.vmap(
                    jax.vmap(one, in_axes=(None, None, None, None, 0, None,
                                           0, None)),
                    in_axes=(0, 0, 0, axes, 0, None, 0, None)))
            return self._cache[cache_key](self.params, stacked.schedule,
                                          n_real, cfg, specs, traffic, keys,
                                          faults)

    # ------------------------------------------------------------ training
    def train_batched(self, stacked_iters: Sequence[StackedApps],
                      cfg: qlearn.QConfig,
                      weights_batch: rewards.RewardWeights,
                      keys,
                      eval_stacked: StackedApps | None = None,
                      faults=None) -> tuple[qlearn.QState, tuple]:
        """Train (K lanes x B agents) in one jitted call.

        ``stacked_iters`` is one StackedApps per training iteration (each
        compiled with its own tile seed, the DES's per-iteration protocol);
        all iterations share one schedule shape.  ``weights_batch`` has
        (B,) leaves, ``keys`` is (K, B, 2).  ``cfg.decay_steps`` may be a
        (K,) array for per-lane decay horizons (lanes differ in
        invocations per iteration).  Returns a QState with (K, B, ...)
        leaves and, when ``eval_stacked`` is given, per-iteration
        (norm_time, norm_mem) histories of shape (K, B, iterations)."""
        self.calls["train"] += 1
        first = stacked_iters[0]
        eval_shape = (None if eval_stacked is None
                      else (eval_stacked.n_phases, eval_stacked.n_threads))
        with jax.profiler.TraceAnnotation("cohm.prep"):
            scheds = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=1),
                *[st.schedule for st in stacked_iters])
            if eval_stacked is not None:
                eval_sched = eval_stacked.schedule
                base = self.baseline(eval_stacked, faults=faults)
                pmask = eval_stacked.phase_mask
                eval_axes = (0, 0, 0)
            else:
                eval_sched = base = pmask = None
                eval_axes = (None, None, None)

            B = keys.shape[1]
            kernel = vec.episode_lowering(self.calls, self.n_lanes * B,
                                          self.fused_step)
            q0 = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (self.n_lanes,) + x.shape),
                qlearn.init_qstate_batch(qlearn.QConfig(), B))
            axes = _cfg_axes(cfg)
            carry_axes = vec.TrainCarry(key=0, it=None, best=0)
            carry0 = vec.TrainCarry(
                key=jnp.asarray(keys), it=jnp.zeros((), jnp.int32),
                best=jnp.full(keys.shape[:2], -jnp.inf, jnp.float32))
        with jax.profiler.TraceAnnotation("cohm.launch"):
            cache_key = ("train_jit", first.n_phases, first.n_threads,
                         eval_shape, tuple(axes), kernel)
            if cache_key not in self._cache:
                train_one = vec.build_train_fn(
                    first.n_phases, first.n_threads, eval_shape,
                    self.cycle_time, demand_cache=True, gated=True,
                    fused=self.fused_step, kernel=kernel)
                # Carry batches (key, best) per agent / per lane; the
                # iteration counter and the FaultSpec replicate everywhere.
                self._cache[cache_key] = jax.jit(_vmap_grid(
                    train_one,
                    lane_axes=(0, 0, *eval_axes, axes, None, carry_axes, 0,
                               None),
                    item_axes=(None, None, None, None, None, None,
                               rewards.RewardWeights(0, 0, 0), carry_axes,
                               0, None),
                    out_axes=(0, carry_axes, 0)))
            qs, _, hist = self._cache[cache_key](
                self.params, scheds, eval_sched, base, pmask, cfg,
                weights_batch, carry0, q0, faults)
        return qs, hist

    def evaluate_batched(self, stacked: StackedApps, qstates: qlearn.QState,
                         cfg: qlearn.QConfig, keys=None, faults=None
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Frozen-greedy evaluation of (K, B) agents vs the per-lane
        NON_COH baseline; returns (norm_time, norm_mem), each (K, B)."""
        base = self.baseline(stacked, faults=faults)
        res = self.episodes(stacked,
                            self.lower_qstates(stacked, qstates),
                            cfg, keys=keys, faults=faults)
        lanes = jax.vmap(jax.vmap(vec.normalized_metrics,
                                  in_axes=(0, None, None)),
                         in_axes=(0, 0, 0))
        return lanes(res, base, stacked.phase_mask)

    # ----------------------------------------------------------- host side
    def lane_phase_metrics(self, stacked: StackedApps,
                           res: vec.EpisodeResult, lane: int
                           ) -> tuple[np.ndarray, np.ndarray]:
        """Lane ``lane``'s real-phase (wall time, off-chip accesses) from a
        stacked EpisodeResult (any leading policy axes are preserved)."""
        n_ph = stacked.compiled[lane].n_phases
        pt = np.asarray(res.phase_time)[lane][..., :n_ph]
        po = np.asarray(res.phase_offchip)[lane][..., :n_ph]
        return pt, po
