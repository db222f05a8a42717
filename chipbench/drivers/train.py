"""Training: ``StackedVecEnv.train_batched`` over (SoC lanes x agents).

Each call trains every lane's agents from a fresh table for the traffic's
iterations, with keys drawn afresh from the run seed; on a mesh of several
chips the same call goes through ``shard.sharded_train_batched_stacked``.
``correct`` compares a seeded sample of the window's calls with the
reference trained from the same keys: the share of agents whose
trajectories forked, and the Q-tables of every agent that did not.
"""
from __future__ import annotations

import functools

import numpy as np

import harness
import reference as ref
import work

SAMPLE_CALLS = 3        # window calls compared with the reference
# Limits of the compared numbers, set from chip readings (PERF.md).
LIMITS = {"forked_agent_share": 0.1, "unforked_qtable_max_gap": 1e-5}


class Training:
    """A cell's training set-up, shared by every verb that trains."""

    def __init__(self, cfg: dict, tparams: dict, seed: int, stream: int,
                 lanes=None):
        import jax.numpy as jnp
        from repro.core import qlearn, rewards

        self.cfg, self.seed = cfg, seed
        self.lanes = lanes or harness.build_lanes(cfg)
        self.iters = tparams["iterations"]
        self.tile_seeds = harness.tile_seeds(seed, self.iters, stream)
        env = self.lanes.env
        self.stacked = [env.compile(self.lanes.train_apps, seed=s)
                        for s in self.tile_seeds]
        self.n_steps = list(self.stacked[0].n_steps)
        self.decay = [n * self.iters for n in self.n_steps]
        self.qcfg = qlearn.QConfig(decay_steps=jnp.asarray(self.decay,
                                                           jnp.int32))
        self.wlist = [w for w in tparams["weightings"]
                      for _ in range(tparams["agents_per_weighting"])]
        self.weights = rewards.stack_weights(
            [rewards.RewardWeights(*w) for w in self.wlist])
        self.shape = (env.n_lanes, len(self.wlist))

    def program_call(self, keys, mesh=None):
        import jax
        from repro.soc import shard

        keys = jax.device_put(keys)
        if mesh is None:
            return self.lanes.env.train_batched(
                self.stacked, self.qcfg, self.weights, keys)[0]
        return shard.sharded_train_batched_stacked(
            self.lanes.env, self.stacked, self.qcfg, self.weights, keys,
            mesh=mesh)[0]

    # ------------------------------------------------------- reference
    @functools.cached_property
    def reference_inputs(self):
        """The reference's lanes and (K, iters, S, ...) schedules."""
        socs = harness.plain_socs(self.cfg)
        lanes = ref.make_lanes(socs, [s.profiles for s in self.lanes.sims])
        flat = [ref.schedule_rows(app, soc["n_mem_tiles"], s)
                for app, soc in zip(self.lanes.train_apps, socs)
                for s in self.tile_seeds]
        stacked, _ = ref.stack_lanes(flat)
        k, i = len(socs), self.iters
        scheds = {n: v.reshape(k, i, *v.shape[1:])
                  for n, v in stacked.items()}
        return lanes, scheds

    def reference_call(self, keys, rnd=ref.identity):
        lanes, scheds = self.reference_inputs
        return ref.train(lanes, scheds, self.decay, np.asarray(self.wlist),
                         keys, rnd)

    def grid_steps(self) -> tuple[int, int]:
        """(real, padded) invocations of one call's stacked schedules."""
        k, b = self.shape
        s_max = int(self.stacked[0].schedule.acc_id.shape[1])
        real = sum(self.n_steps) * b * self.iters
        return real, k * s_max * b * self.iters - real

    def work(self) -> tuple[float, float]:
        """(bytes, operations) of one call's real invocations."""
        b = self.shape[1] * self.iters
        tot_b = tot_o = 0.0
        for c, n in zip(self.stacked[0].compiled, self.n_steps):
            by, op = work.per_invocation(c.n_threads, c.schedule.tiles.shape[1])
            tot_b += by * n * b
            tot_o += op * n * b
        return tot_b, tot_o


def compare_training(prog, refq) -> dict:
    """The compared numbers of one training call.

    An agent whose visit counts differ from the reference's took another
    action somewhere: a greedy choice between two Q-values within a
    rounding of each other can fall either way, after which the two
    trajectories part (a fork).  Forks are counted; every agent that did
    not fork must match the reference's Q-table to rounding."""
    q_p, v_p = (np.asarray(prog[0]), np.asarray(prog[1]))
    q_r, v_r = (np.asarray(refq[0]), np.asarray(refq[1]))
    forked = np.any(v_p != v_r, axis=(-1, -2))
    gap = np.max(np.abs(q_p - q_r), axis=(-1, -2))
    return {"forked_agent_share": float(forked.mean()),
            "unforked_qtable_max_gap": float(gap[~forked].max())
            if (~forked).any() else 0.0}


def worst(readings: list[dict]) -> dict:
    return {k: max(r[k] for r in readings) for k in readings[0]}


class Driver:
    pulls = False

    def __init__(self, cfg, traffic, seed, devices, scale=None):
        traffic = dict(traffic, **(scale or {}))
        self.seed = seed
        self.tr = Training(cfg, traffic, seed, stream=1)
        self.mesh = None
        if traffic["mesh"] > 1:
            from repro.soc import shard
            self.mesh = shard.lane_mesh(devices[:traffic["mesh"]])
        real, pad = self.tr.grid_steps()
        self.invocations_per_call = real
        self.padded_per_call = pad
        self.outputs = []

    def keys(self, i: int):
        return harness.raw_keys(self.seed, self.tr.shape, 2, i)

    def warm(self):
        import jax
        jax.block_until_ready(
            self.tr.program_call(self.keys(1 << 30), self.mesh))

    def dispatch(self, i):
        qs = self.tr.program_call(self.keys(i), self.mesh)
        self.outputs.append(qs)
        return qs

    def work(self):
        return self.tr.work()

    def collect(self, n_calls: int):
        self.sampled = harness.sample_outputs(self.seed, self.outputs,
                                              SAMPLE_CALLS)

    def check(self, control: bool = False) -> dict:
        """Compared numbers over the sampled calls.  ``control`` puts the
        reference computed at bfloat16 in the program's place."""
        readings = []
        for i, qs in self.sampled.items():
            refq = self.tr.reference_call(self.keys(i))
            prog = (self.tr.reference_call(self.keys(i), ref.to_bf16)
                    if control else (qs.qtable, qs.visits))
            readings.append(compare_training(prog, refq))
        return worst(readings)
