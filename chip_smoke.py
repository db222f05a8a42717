"""Smoke run of the batched Cohmeleon main path on one TPU chip.

    python chip_smoke.py              # one chip: train, eval, serve
    python chip_smoke.py --chips 4    # four chips: the lane-sharded trainer

Each phase drives the library's own entry points at the sizes the figures
run, through the default dispatch (the fused XLA scan for the batched
training and evaluation calls, the Pallas serve kernel for serving), and
then makes the same call on an environment built with
``fused_step=False`` (the unfused XLA scan) on the same chip:

* train: ``StackedVecEnv.train_batched`` over Fig. 9's eight (SoC,
  flavor) lanes at ``n_phases=8``, B = 8 agents (2 reward weightings x
  4 seeds), 3 training iterations;
* eval: one ``StackedVecEnv.episodes`` call over the whole policy suite
  (the four fixed modes, random, manual and the 8 trained agents);
* serve: one ``ServeEnv.serve`` chunk of 1024 requests of Fig. 11's
  2-tenant bursty traffic on SoC1, with a frozen trained table agent.

Checks: the deterministic families (fixed modes, manual) agree per phase
in time and off-chip accesses within 1e-4 relative; serving admits and
sheds the same requests; the trained Q-tables agree within 1e-5 and the
learned agents choose the same mode on at least 99% of invocations.
``--chips 4`` runs only ``shard.sharded_train_batched_stacked`` over four
chips against the same call on one: integer state bitwise, floats within
1e-6, the output sharded over all four.

Each call is timed twice: ``setup_s`` is the first call (compilation
included), ``wall_s`` the second.  The last line of standard output is
one JSON object naming the device; the script exits non-zero without it
when JAX finds no TPU or any check fails.  The persistent compilation
cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else
``.jax_cache`` in this directory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REL_TOL = 1e-4       # deterministic families, fused vs unfused step
SHARD_ATOL = 1e-6    # float leaves, four chips vs one
QTABLE_ATOL = 1e-5   # trained Q-tables, fused vs unfused step
MIN_MATCH = 0.99     # learned agents' mode-match share, fused vs unfused
N_PHASES = 8         # the full Fig. 9 application length
ITERS = 3
SEEDS = 4
N_REQUESTS = 1024
QUEUE_CAP = 8


def _timed(fn):
    """(result, first-call seconds, second-call seconds)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def _report_times(phase, ker, ref):
    print(f"{phase} times: fused setup_s={ker[0]:.3f} wall_s={ker[1]:.3f} "
          f"ref setup_s={ref[0]:.3f} wall_s={ref[1]:.3f}")


class Fig9Setup:
    """Fig. 9's lanes, training schedules and evaluation application."""

    def __init__(self, n_phases=N_PHASES, iters=ITERS, seeds=SEEDS):
        from benchmarks.fig9_socs import SOC_FLAVORS, _eval_app
        from repro.core import qlearn, rewards
        from repro.soc.apps import make_application
        from repro.soc.config import SOCS
        from repro.soc.des import SoCSimulator
        from repro.soc.stacked import StackedVecEnv

        self.flavors = SOC_FLAVORS
        self.sims = [SoCSimulator(SOCS[n], seed=1, flavor=f)
                     for n, f in SOC_FLAVORS]
        self.env = StackedVecEnv.from_simulators(self.sims)
        self.env_ref = StackedVecEnv(self.env.socs, envs=self.env.envs,
                                     fused_step=False)
        apps = [make_application(sim.soc, seed=0, n_phases=n_phases)
                for sim in self.sims]
        self.stacked_iters = [self.env.compile(apps, seed=it)
                              for it in range(iters)]
        self.cfg = qlearn.QConfig(decay_steps=jnp.asarray(
            [s * iters for s in self.stacked_iters[0].n_steps], jnp.int32))
        weightings = [rewards.PAPER_DEFAULT_WEIGHTS,
                      rewards.RewardWeights(0.5, 0.25, 0.25)]
        self.weights = rewards.stack_weights(
            [w for w in weightings for _ in range(seeds)])
        k, b = self.env.n_lanes, len(weightings) * seeds
        self.keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(k * b)).reshape(k, b, 2)
        self.iters = iters
        self.eval_stacked = self.env.compile(
            [_eval_app(sim, n, n_phases)
             for sim, (n, _) in zip(self.sims, SOC_FLAVORS)], seed=4)

    def train(self, env):
        return env.train_batched(self.stacked_iters, self.cfg, self.weights,
                                 self.keys)[0]


def phase_train(fx: Fig9Setup) -> tuple[bool, object]:
    qs, *ker = _timed(lambda: fx.train(fx.env))
    qs_ref, *ref = _timed(lambda: fx.train(fx.env_ref))
    _report_times("train", ker, ref)
    # Every valid invocation of every iteration counts one visit, whatever
    # the agent chose: the totals are exact on both paths.
    want = np.asarray(fx.stacked_iters[0].n_steps) * fx.iters
    visits = np.asarray(qs.visits).sum(axis=(2, 3))
    visits_ref = np.asarray(qs_ref.visits).sum(axis=(2, 3))
    counts_ok = bool(np.all(visits == want[:, None])
                     and np.all(visits_ref == want[:, None]))
    finite = bool(np.isfinite(np.asarray(qs.qtable)).all())
    dq = float(np.max(np.abs(np.asarray(qs.qtable)
                             - np.asarray(qs_ref.qtable))))
    print(f"train check: agents={visits.size} visit_totals_exact="
          f"{counts_ok} qtable_finite={finite} "
          f"qtable_max_abs_diff_vs_ref={dq:.6g} (tol {QTABLE_ATOL:g})")
    return counts_ok and finite and dq <= QTABLE_ATOL, qs


def phase_eval(fx: Fig9Setup, qs) -> bool:
    from repro.core.modes import CoherenceMode
    from repro.core.policies import (FixedHomogeneous, ManualPolicy,
                                     RandomPolicy)

    env, stacked = fx.env, fx.eval_stacked
    suite = [FixedHomogeneous(m) for m in CoherenceMode]
    suite += [ManualPolicy(), RandomPolicy()]
    n_det = len(suite) - 1          # fixed modes + manual
    base = env.lower(stacked, suite)
    agents = env.lower_qstates(stacked, qs)
    specs = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=1), base, agents)
    keys = env._default_keys(*specs.learned.shape)
    res, *ker = _timed(lambda: env.episodes(stacked, specs, fx.cfg, keys))
    res_ref, *ref = _timed(
        lambda: fx.env_ref.episodes(stacked, specs, fx.cfg, keys))
    _report_times("eval", ker, ref)

    rel_t = rel_o = 0.0
    for k in range(env.n_lanes):
        pt, po = env.lane_phase_metrics(stacked, res, k)
        rt, ro = env.lane_phase_metrics(stacked, res_ref, k)
        rel_t = max(rel_t, float(np.max(
            np.abs(pt[:n_det] - rt[:n_det])
            / np.maximum(np.abs(rt[:n_det]), 1e-30))))
        rel_o = max(rel_o, float(np.max(
            np.abs(po[:n_det] - ro[:n_det])
            / np.maximum(np.abs(ro[:n_det]), 1e-30))))
    valid = np.asarray(stacked.schedule.valid)[:, None, :]
    same = np.asarray(res.mode) == np.asarray(res_ref.mode)
    learned = np.s_[:, n_det:]
    match = float((same[learned] & valid).sum()
                  / np.broadcast_to(valid, same[learned].shape).sum())
    det_same = bool(np.all(same[:, :n_det] | ~valid))
    ok = (rel_t <= REL_TOL and rel_o <= REL_TOL and det_same
          and match >= MIN_MATCH)
    print(f"eval check: lanes={env.n_lanes} policies="
          f"{specs.learned.shape[1]} deterministic max_rel_phase_time="
          f"{rel_t:.3g} max_rel_phase_offchip={rel_o:.3g} (tol {REL_TOL:g}) "
          f"modes_identical={det_same}")
    print(f"eval check: learned (random + trained) mode_match_share="
          f"{match:.6f} (min {MIN_MATCH:g})")
    return ok


def phase_serve(fx: Fig9Setup, qs) -> bool:
    from benchmarks.fig11_serving import SOC_NAME, _traffic
    from repro.core import qlearn
    from repro.soc import traffic, vecenv

    lane = [n for n, _ in fx.flavors].index(SOC_NAME)
    env = fx.env.envs[lane]
    env_ref = vecenv.VecEnv.from_simulator(fx.sims[lane], fused_step=False)
    serve_env = vecenv.ServeEnv(env, queue_cap=QUEUE_CAP,
                                n_requests=N_REQUESTS)
    serve_ref = vecenv.ServeEnv(env_ref, queue_cap=QUEUE_CAP,
                                n_requests=N_REQUESTS)
    compiled = fx.eval_stacked.compiled[lane]
    agent = qlearn.freeze(jax.tree_util.tree_map(lambda x: x[lane, 0], qs))
    spec = env.lower(compiled, "q", qstate=agent)
    cfg = fx.cfg._replace(decay_steps=fx.cfg.decay_steps[lane])
    key = jax.random.PRNGKey(7)

    # Fig. 11's traffic at its naive capacity: an idle probe gives the
    # mean service time, the offered rate is n_accs / mean_exec, and the
    # sensitive tenant's deadline is one full queue drain.
    _, _, probe = serve_env.serve(compiled, spec, traffic.poisson(1e-9),
                                  cfg=cfg, key=key)
    ex = np.asarray(probe.executed)
    svc = float(np.asarray(probe.exec_time)[ex].mean())
    tspec = _traffic(env.soc.n_accs / svc, QUEUE_CAP * svc, 0.25 * svc)

    def run(se):
        return se.serve(compiled, spec, tspec, cfg=cfg, key=key)[2]

    res, *ker = _timed(lambda: run(serve_env))
    res_ref, *ref = _timed(lambda: run(serve_ref))
    _report_times("serve", ker, ref)
    adm = np.asarray(res.executed)
    adm_ref = np.asarray(res_ref.executed)
    same = bool(np.array_equal(adm, adm_ref))
    print(f"serve check: requests={adm.size} admitted={int(adm.sum())} "
          f"shed={int((~adm).sum())} ref_admitted={int(adm_ref.sum())} "
          f"ref_shed={int((~adm_ref).sum())} decisions_identical={same}")
    return same and 0 < int(adm.sum())


def phase_shard4(fx: Fig9Setup) -> bool:
    from repro.soc import shard

    devs = jax.devices()[:4]
    if len(devs) < 4:
        print(f"shard4: needs 4 devices, found {len(devs)}", file=sys.stderr)
        return False

    def run(devices):
        return shard.sharded_train_batched_stacked(
            fx.env, fx.stacked_iters, fx.cfg, fx.weights, fx.keys,
            mesh=shard.lane_mesh(devices))[0]

    qs4, *t4 = _timed(lambda: run(devs))
    qs1, *t1 = _timed(lambda: run(devs[:1]))
    print(f"shard4 times: 4-chip setup_s={t4[0]:.3f} wall_s={t4[1]:.3f} "
          f"1-chip setup_s={t1[0]:.3f} wall_s={t1[1]:.3f}")
    ints_ok, fdiff = True, 0.0
    for a, b in zip(jax.tree_util.tree_leaves(qs4),
                    jax.tree_util.tree_leaves(qs1)):
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(a.dtype, np.floating):
            fdiff = max(fdiff, float(np.max(np.abs(a - b))))
        else:
            ints_ok &= bool(np.array_equal(a, b))
    sh = qs4.qtable.sharding
    spread = len(sh.device_set) == 4 and not sh.is_fully_replicated
    print(f"shard4 check: agents_per_chip={fx.keys.shape[1] // 4} "
          f"integer_state_bitwise={ints_ok} float_max_abs_diff={fdiff:.3g} "
          f"(tol {SHARD_ATOL:g}) qtable_sharding_devices="
          f"{len(sh.device_set)} spread={spread}")
    return ints_ok and fdiff <= SHARD_ATOL and spread


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={n_dev}")

    fx = Fig9Setup()
    if args.chips == 4:
        ok = phase_shard4(fx)
    else:
        ok_train, qs = phase_train(fx)
        ok = ok_train & phase_eval(fx, qs) & phase_serve(fx, qs)
    if not ok:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
