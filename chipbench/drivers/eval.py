"""Evaluation: ``StackedVecEnv.episodes`` over (SoC lanes x policies).

Set-up trains the agents as the training cell does (one call), lowers the
fixed modes, the manual heuristic, the random policy and the frozen agents
into one spec batch; each window call evaluates every (lane, policy)
episode with keys drawn afresh.  ``correct`` compares a seeded sample of
calls with the reference, which trains its own agents from the same
keys: the share of episodes whose modes forked, and the per-phase time
and off-chip count of every episode that did not.
"""
from __future__ import annotations

import functools

import numpy as np

import harness
import reference as ref
import work
from drivers.train import Training, worst

SAMPLE_CALLS = 3
LIMITS = {"forked_episode_share": 0.1, "unforked_phase_max_rel_gap": 1e-5}


def compare_episodes(prog, refr, phase_mask, valid) -> dict:
    """``prog``/``refr``: (phase_time, phase_offchip, mode) with (K, N, ..)
    leaves; ``phase_mask`` (K, P) real phases, ``valid`` (K, S) rows.

    An episode whose modes differ from the reference's forked (a learned
    agent trained on another trajectory, see ``train.compare_training``);
    forks are counted, and every episode that did not fork must match the
    reference's per-phase time and off-chip count to rounding."""
    pt, po, pm = (np.asarray(x) for x in prog)
    rt, ro, rm = (np.asarray(x) for x in refr)
    forked = np.any((pm != rm) & valid[:, None, :], axis=-1)
    m = np.broadcast_to(phase_mask[:, None, :], pt.shape)
    gap_t = np.abs(pt - rt) / np.maximum(np.abs(rt), 1e-12)
    gap_o = np.abs(po - ro) / np.maximum(np.abs(ro), 1.0)
    gap = np.max(np.where(m, np.maximum(gap_t, gap_o), 0.0), axis=-1)
    return {"forked_episode_share": float(forked.mean()),
            "unforked_phase_max_rel_gap": float(gap[~forked].max())
            if (~forked).any() else 0.0}


class Driver:
    pulls = False

    def __init__(self, cfg, traffic, seed, devices, scale=None):
        import jax
        import jax.numpy as jnp
        from repro.core.modes import CoherenceMode
        from repro.core.policies import (FixedHomogeneous, ManualPolicy,
                                         RandomPolicy)

        tparams = dict(traffic["train"], **(scale or {}))
        self.seed, self.cfg = seed, cfg
        self.tr = Training(cfg, tparams, seed, stream=1)
        env = self.tr.lanes.env
        self.eval_seed = harness.tile_seeds(seed, 1, 3)[0]
        self.apps = harness.eval_apps(cfg, self.tr.lanes.sims)
        self.stacked = env.compile(self.apps, seed=self.eval_seed)
        self.train_keys = harness.raw_keys(seed, self.tr.shape, 2, 0)
        self.agents = self.tr.program_call(self.train_keys)
        suite = [FixedHomogeneous(CoherenceMode(m))
                 for m in traffic["fixed_modes"]]
        self.kinds = list(traffic["fixed_modes"])
        if traffic["manual"]:
            suite.append(ManualPolicy())
            self.kinds.append(4)
        if traffic["random"]:
            suite.append(RandomPolicy())
            self.kinds.append(-1)
        n_agents = self.tr.shape[1]
        self.learned = [False] * len(self.kinds)
        if traffic["random"]:
            self.learned[-1] = True
        self.kinds += [-1] * n_agents
        self.learned += [True] * n_agents
        base = env.lower(self.stacked, suite)
        agents = env.lower_qstates(self.stacked, self.agents)
        self.specs = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=1), base, agents)
        self.shape = (env.n_lanes, len(self.kinds))
        n_real = list(self.stacked.n_steps)
        s_max = int(self.stacked.schedule.acc_id.shape[1])
        self.invocations_per_call = sum(n_real) * self.shape[1]
        self.padded_per_call = (len(n_real) * s_max * self.shape[1]
                                - self.invocations_per_call)
        self.outputs = []
        self._ref_tables = {}

    def keys(self, i: int):
        return harness.raw_keys(self.seed, self.shape, 4, i)

    def _call(self, keys):
        import jax
        res = self.tr.lanes.env.episodes(self.stacked, self.specs,
                                         self.tr.qcfg, jax.device_put(keys))
        return res.phase_time, res.phase_offchip, res.mode

    def warm(self):
        import jax
        jax.block_until_ready(self._call(self.keys(1 << 30)))

    def dispatch(self, i):
        out = self._call(self.keys(i))
        self.outputs.append(out)
        return out

    def work(self):
        tot_b = tot_o = 0.0
        for c in self.stacked.compiled:
            by, op = work.per_invocation(c.n_threads,
                                         c.schedule.tiles.shape[1])
            tot_b += by * c.n_steps * self.shape[1]
            tot_o += op * c.n_steps * self.shape[1]
        return tot_b, tot_o

    def collect(self, n_calls: int):
        self.sampled = harness.sample_outputs(self.seed, self.outputs,
                                              SAMPLE_CALLS)

    def reference_tables(self, rnd):
        """The reference's own agents, trained from the set-up keys, behind
        the untrained frozen tables of the other policies."""
        if rnd not in self._ref_tables:
            q, _, _ = self.tr.reference_call(self.train_keys, rnd)
            k, b = self.tr.shape
            ones = np.full((k, self.shape[1] - b) + q.shape[2:], ref.Q_INIT,
                           np.float32)
            self._ref_tables[rnd] = np.concatenate([ones, np.asarray(q)],
                                                   axis=1)
        return self._ref_tables[rnd]

    @functools.cached_property
    def reference_schedule(self):
        socs = harness.plain_socs(self.cfg)
        rows = [ref.schedule_rows(a, s["n_mem_tiles"], self.eval_seed)
                for a, s in zip(self.apps, socs)]
        n_phases = [r["n_phases"] for r in rows]
        mask = np.arange(max(n_phases))[None, :] < np.asarray(n_phases)[:, None]
        return ref.stack_lanes(rows)[0], mask

    def reference(self, keys, rnd=ref.identity):
        lanes, _ = self.tr.reference_inputs
        sched, mask = self.reference_schedule
        pt, po, y = ref.evaluate(lanes, sched, self.reference_tables(rnd),
                                 self.learned, self.kinds, keys,
                                 mask.shape[1], rnd)
        return pt, po, np.asarray(y)[..., 0].astype(np.int32)

    def check(self, control: bool = False) -> dict:
        sched, mask = self.reference_schedule
        valid = sched["valid"]
        readings = []
        for i, out in self.sampled.items():
            refr = self.reference(self.keys(i))
            prog = (self.reference(self.keys(i), ref.to_bf16) if control
                    else out)
            readings.append(compare_episodes(prog, refr, mask, valid))
        return worst(readings)
