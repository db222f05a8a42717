"""Serving: ``ServeEnv.serve`` chunk after chunk of one long stream.

Set-up trains one agent as the training cell does and freezes it; the
window serves the traffic's stream in chunks, the carry, clock and chunk
keys crossing chunk boundaries as in ``ServeEnv.serve_checkpointed``.
Each chunk's result is pulled to the host before the next is issued.
``correct`` replays the stream's first chunks in the reference (which
trains its own agent from the same keys) and compares every request's
admission, mode and latency.
"""
from __future__ import annotations

import numpy as np

import harness
import reference as ref
import work
from drivers.train import Training

LIMITS = {"decision_mismatch": 0, "latency_max_rel_gap": 1e-4,
          "mode_mismatch_share": 0.005}


def compare_chunks(prog: list, refr: list) -> dict:
    """``prog``/``refr``: per chunk (executed, mode, latency) arrays."""
    ex_p = np.concatenate([p[0] for p in prog])
    ex_r = np.concatenate([r[0] for r in refr])
    both = ex_p & ex_r
    m_p = np.concatenate([p[1] for p in prog])
    m_r = np.concatenate([r[1] for r in refr])
    l_p = np.concatenate([p[2] for p in prog])
    l_r = np.concatenate([r[2] for r in refr])
    gap = np.abs(l_p - l_r) / np.maximum(np.abs(l_r), 1.0)
    return {"decision_mismatch": int((ex_p != ex_r).sum()),
            "latency_max_rel_gap": float(gap[both].max()) if both.any()
            else 0.0,
            "mode_mismatch_share": float((m_p != m_r)[both].mean())
            if both.any() else 0.0}


class Driver:
    pulls = True

    def __init__(self, cfg, traffic, seed, devices, scale=None):
        import jax
        import jax.numpy as jnp
        from repro.core import qlearn
        from repro.soc import traffic as traffic_mod
        from repro.soc import vecenv
        from repro.soc.apps import make_application

        traffic = dict(traffic, **(scale or {}))
        tparams = dict(traffic["train"], **(scale or {}))
        self.seed, self.cfg, self.traffic = seed, cfg, traffic
        self.tr = Training(cfg, tparams, seed, stream=1)
        self.train_keys = harness.raw_keys(seed, self.tr.shape, 2, 0)
        qs = self.tr.program_call(self.train_keys)
        agent = qlearn.freeze(jax.tree_util.tree_map(lambda x: x[0, 0], qs))
        sim = self.tr.lanes.sims[0]
        a = cfg["serve_app"]
        self.app = make_application(sim.soc, seed=a["seed"],
                                    n_phases=a["n_phases"])
        self.row_seed = harness.tile_seeds(seed, 1, 3)[0]
        self.compiled = vecenv.compile_app(self.app, sim.soc,
                                           seed=self.row_seed)
        env = self.tr.lanes.env.envs[0]
        self.n = traffic["requests_per_chunk"]
        self.serve_env = vecenv.ServeEnv(env, queue_cap=cfg["queue_cap"],
                                         n_requests=self.n)
        self.spec = env.lower(self.compiled, "q", qstate=agent)
        self.qcfg = qlearn.QConfig(decay_steps=self.tr.decay[0])
        t = traffic
        self.tr_key = harness.raw_keys(seed, (), 5)
        self.key = harness.raw_keys(seed, (), 6)
        self.tspec = traffic_mod.bursty(
            t["rate_per_cycle"], burst_rate=t["burst_rate"],
            p_burst=t["p_burst"], p_calm=t["p_calm"], mix=tuple(t["mix"]),
            deadline=jnp.asarray(t["deadline_cycles"], jnp.float32),
            priority=jnp.asarray(t["priority"], jnp.float32),
            backoff=t["backoff_cycles"], overload_frac=t["overload_frac"],
            pressure_beta=t["pressure_beta"],
            prio_reserve=t["prio_reserve"], key=jnp.asarray(self.tr_key))
        self.invocations_per_call = self.n
        self.padded_per_call = 0
        self.n_compare = traffic["compare_chunks"]
        self.reset()

    def reset(self):
        """A fresh stream: idle devices, the agent's table, clock 0."""
        self.carry = self.serve_env.init_carry(self.spec.qstate)
        self.qs = self.spec.qstate
        self.t0 = np.float32(0.0)
        self.kept = []

    def dispatch(self, i):
        import jax
        from repro.soc import traffic as traffic_mod

        self.carry, self.qs, res = self.serve_env.serve(
            self.compiled, self.spec._replace(qstate=self.qs),
            traffic_mod.chunk_key(self.tspec, i), cfg=self.qcfg,
            key=jax.random.fold_in(self.key, i), carry=self.carry,
            t0=self.t0)
        return res

    def pull(self, i, out):
        import jax
        host = jax.device_get(out)
        self.t0 = host.t_arr[-1]
        if i < self.n_compare:
            self.kept.append((host.executed, host.mode, host.latency))

    def warm(self):
        import jax
        out = jax.block_until_ready(self.dispatch(0))
        self.pull(0, out)
        self.reset()

    def work(self):
        by, op = work.per_invocation(self.serve_env.env.soc.n_accs,
                                     self.compiled.schedule.tiles.shape[1],
                                     serve=True,
                                     queue_cap=self.cfg["queue_cap"])
        return by * self.n, op * self.n

    def collect(self, n_calls: int):
        self.sampled = list(self.kept)

    def reference(self, n_chunks: int, rnd=ref.identity):
        import jax
        import jax.numpy as jnp

        socs = harness.plain_socs(self.cfg)
        lanes, _ = self.tr.reference_inputs
        q, _, steps = self.tr.reference_call(self.train_keys, rnd)
        sched = ref.schedule_rows(self.app, socs[0]["n_mem_tiles"],
                                  self.row_seed)
        sched = {k: jnp.asarray(v) for k, v in sched.items()
                 if isinstance(v, np.ndarray)}
        t = self.traffic
        tr = ref.Traffic(*[jnp.asarray(v, jnp.float32) for v in (
            t["rate_per_cycle"], t["burst_rate"], t["p_burst"], t["p_calm"],
            t["mix"], t["deadline_cycles"], t["priority"],
            t["backoff_cycles"], t["overload_frac"], t["pressure_beta"],
            t["prio_reserve"])])
        queues = ref.init_queues(q[0, 0], socs[0]["n_accs"],
                                 socs[0]["n_mem_tiles"], self.cfg["queue_cap"],
                                 steps[0, 0])
        fn = ref.serve_chunk_fn(self.n, rnd)
        t0 = jnp.zeros((), jnp.float32)
        out = []
        for i in range(n_chunks):
            queues, y, t_arr, _ = fn(
                lanes.static[0], lanes.pmat[0], lanes.masks[0], sched, True,
                True, jnp.float32(self.tr.decay[0]), tr, queues,
                jax.random.fold_in(self.key, i),
                jax.random.fold_in(self.tr_key, i), t0)
            t0 = t_arr[-1]
            y = np.asarray(y)
            cols = {c: y[:, j] for j, c in enumerate(ref.SERVE_COLS)}
            out.append((cols["executed"] > 0, cols["mode"].astype(np.int32),
                        cols["latency"]))
        return out

    def check(self, control: bool = False) -> dict:
        n = len(self.sampled)
        refr = self.reference(n)
        prog = self.reference(n, ref.to_bf16) if control else self.sampled
        return compare_chunks(prog, refr)
