"""Neural policy subsystem (soc.nn): the function-approximation Q agent.

Contracts, from unit to end-to-end:

  * the packed-weight forward/backward pair is shape-correct, the one-hot
    distillation of a Q-table reproduces gathered table rows exactly, and
    the semi-gradient TD update moves Q(s, a) toward R while frozen /
    ungated / non-finite updates are bitwise no-ops;
  * an MLP PolicySpec runs bitwise-equivalently through the fused and
    unfused episode lowerings on the integer traces (modes, states,
    actions, step counters), with float traces and the TD-updated weight
    pack agreeing to ~1 ULP (XLA contracts FMAs differently across the
    two scan bodies on CPU);
  * non-finite weights degrade every step to NON_COH through the
    existing non-finite-row fallback (the PR-7 fault contract);
  * the DES host mirror (MLPQPolicy.decide) selects the same modes as
    the lowered spec on single-thread apps — the fidelity cross-check
    the tabular families already pin;
  * serving carries and trains the weights in ServeCarry.wpack;
  * the portfolio trainer learns across (SoC x app) pairs and is
    crash-resumable: interrupted + resumed == uninterrupted, bitwise.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import qlearn, vops
from repro.checkpoint.manager import CheckpointManager
from repro.soc import nn as socnn, vecenv as vec
from repro.soc.apps import make_phase
from repro.soc.config import SOCS, SOC_MOTIV_ISO, SOC_MOTIV_PAR
from repro.soc.des import Application, SoCSimulator

TILE_SEED = 11


def _chain_app(soc, seed, n_threads=1, n_phases=3):
    rng = np.random.default_rng(seed)
    phases = [
        make_phase(rng, soc, name=f"p{i}", n_threads=n_threads,
                   size_classes=[c], chain_len=3, loops=2)
        for i, c in enumerate(("S", "M", "L")[:n_phases])
    ]
    return Application(name=f"{soc.name}-nnchain", phases=phases)


# ------------------------------------------------------------------- units
def test_pack_shape_and_forward_shape():
    cfg = socnn.MLPConfig()
    dims = socnn.mlp_dims(cfg)
    assert dims == (socnn.N_SENSE_FEATURES, 16, 16, 4)
    rows, cols = socnn.pack_shape(dims)
    assert (rows, cols) == (sum(d + 1 for d in dims[:-1]), 16)
    mlp = socnn.init_mlp_qstate(jax.random.PRNGKey(0), cfg)
    assert mlp.wpack.shape == (rows, cols)
    x = jnp.linspace(0.0, 1.0, dims[0])
    row = socnn.forward_packed(mlp.wpack, x, dims)
    assert row.shape == (4,) and bool(jnp.all(jnp.isfinite(row)))


def test_fresh_network_is_all_tie_at_optimistic_init():
    """Output layer starts at W=0, b=q_init, so every Q-row is the tabular
    optimistic all-tie — untrained MLP == Random policy under the
    randomized-argmax selection (the paper's iteration-0 property)."""
    for ctor in (lambda: socnn.init_mlp_qstate(jax.random.PRNGKey(3)),
                 socnn.frozen_mlp_qstate):
        mlp = ctor()
        dims = socnn.mlp_dims(mlp.cfg)
        for t in np.linspace(0.0, 1.0, 5):
            x = jnp.full((dims[0],), jnp.float32(t))
            row = socnn.forward_packed(mlp.wpack, x, dims)
            np.testing.assert_array_equal(np.asarray(row), np.ones(4))
    # the placeholder is deterministic — two builds are bitwise-identical
    a, b = socnn.frozen_mlp_qstate(), socnn.frozen_mlp_qstate()
    np.testing.assert_array_equal(np.asarray(a.wpack), np.asarray(b.wpack))
    assert bool(a.frozen) and float(a.lr) == 0.0


def test_onehot_distillation_reproduces_table_rows_exactly():
    rng = np.random.default_rng(0)
    qtable = jnp.asarray(rng.normal(size=(243, 4)), jnp.float32)
    mlp = socnn.mlp_from_qtable(qtable)
    dims = socnn.mlp_dims(mlp.cfg)
    for s in (0, 7, 100, 242):
        x = (vops.iota(243) == s).astype(jnp.float32)
        row = socnn.forward_packed(mlp.wpack, x, dims)
        np.testing.assert_array_equal(np.asarray(row),
                                      np.asarray(qtable[s]))


def test_td_update_moves_q_toward_reward_and_gates_are_noops():
    cfg = socnn.MLPConfig()
    dims = socnn.mlp_dims(cfg)
    mlp = socnn.init_mlp_qstate(jax.random.PRNGKey(1), cfg)
    x = jnp.linspace(0.1, 0.9, dims[0])
    action, reward = jnp.asarray(2, jnp.int32), jnp.float32(0.25)

    def q_a(wp):
        return float(socnn.forward_packed(wp, x, dims)[2])

    d0 = abs(q_a(mlp.wpack) - 0.25)
    wp = mlp.wpack
    for _ in range(20):
        wp = socnn.td_update_packed(wp, x, action, reward,
                                    jnp.float32(0.05), dims,
                                    jnp.asarray(True))
    assert abs(q_a(wp) - 0.25) < 0.2 * d0
    # gate off / zero step size / non-finite reward: bitwise no-ops
    for kw in ((jnp.float32(0.05), jnp.asarray(False)),
               (jnp.float32(0.0), jnp.asarray(True)),):
        out = socnn.td_update_packed(mlp.wpack, x, action, reward,
                                     kw[0], dims, kw[1])
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(mlp.wpack))
    out = socnn.td_update_packed(mlp.wpack, x, action, jnp.float32(np.nan),
                                 jnp.float32(0.05), dims, jnp.asarray(True))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(mlp.wpack))


def test_mlp_config_is_static_treedef():
    """MLPConfig rides the treedef: vmap/tree_map skip it and stacking
    states with mismatched configs fails at the structure level."""
    a = socnn.init_mlp_qstate(jax.random.PRNGKey(0))
    b = socnn.init_mlp_qstate(jax.random.PRNGKey(1))
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), a, b)
    assert stacked.cfg is a.cfg
    c = socnn.mlp_from_qtable(jnp.zeros((243, 4)))
    with pytest.raises(ValueError):
        jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), a, c)


# ------------------------------------------------- episode-level contracts
@pytest.fixture(scope="module")
def nn_env():
    soc = SOC_MOTIV_PAR
    app = _chain_app(soc, seed=6, n_threads=2)
    compiled = vec.compile_app(app, soc, seed=TILE_SEED)
    mlp = socnn.init_mlp_qstate(jax.random.PRNGKey(7))
    return soc, app, compiled, mlp


def test_mlp_episode_fused_unfused_equivalence(nn_env):
    """The two episode lowerings take identical decisions everywhere
    (modes, states, actions, step counters — exact), and their float
    traces / trained packs agree to ~1 ULP: the extra network ops change
    how XLA contracts FMAs in the surrounding timing model, so full
    bitwise equality holds only for the table families (pinned in
    test_vecenv_equivalence)."""
    soc, _, compiled, mlp = nn_env
    cfg = qlearn.QConfig(decay_steps=compiled.n_steps)
    out = {}
    for fused in (False, True):
        env = vec.VecEnv(soc, seed=0, fused_step=fused)
        spec = vec.mlp_policy_spec(mlp, compiled.schedule)
        out[fused] = env.episode_spec(compiled, spec, cfg=cfg,
                                      key=jax.random.PRNGKey(3))
    (qs_a, mlp_a), res_a = out[False]
    (qs_b, mlp_b), res_b = out[True]
    np.testing.assert_array_equal(np.asarray(res_a.mode),
                                  np.asarray(res_b.mode))
    np.testing.assert_array_equal(np.asarray(res_a.state_idx),
                                  np.asarray(res_b.state_idx))
    assert int(mlp_a.step) == int(mlp_b.step) > 0
    # the (placeholder) table is untouched on both paths — bitwise
    np.testing.assert_array_equal(np.asarray(qs_a.qtable),
                                  np.asarray(qs_b.qtable))
    np.testing.assert_allclose(np.asarray(mlp_a.wpack),
                               np.asarray(mlp_b.wpack), rtol=0, atol=1e-6)
    for fld in ("exec_time", "offchip", "reward", "phase_time",
                "phase_offchip"):
        a, b = getattr(res_a, fld), getattr(res_b, fld)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=1e-6, err_msg=fld)
    assert bool(jnp.any(mlp_b.wpack != mlp.wpack))  # it actually learned


def test_non_finite_weights_degrade_to_non_coh(nn_env):
    soc, _, compiled, mlp = nn_env
    bad = socnn.freeze(mlp._replace(
        wpack=mlp.wpack.at[0, 0].set(jnp.nan)))
    for fused in (False, True):
        env = vec.VecEnv(soc, seed=0, fused_step=fused)
        spec = vec.mlp_policy_spec(bad, compiled.schedule)
        (_, _), res = env.episode_spec(compiled, spec,
                                       key=jax.random.PRNGKey(0))
        assert np.all(np.asarray(res.mode) == 0), fused


@pytest.mark.parametrize("socname", ["SoC-motiv-iso", "SoC1"])
def test_mlp_des_fidelity_single_thread(socname):
    """MLPQPolicy.decide (host features + greedy argmax) picks the same
    modes as the lowered qfun spec on single-thread apps, where the
    concurrent-set features are trivially equal — the same DES-vs-vecenv
    fidelity contract the tabular families pin.  The network is briefly
    trained first: a fresh one is an exact all-tie everywhere, where
    selection is *defined* to tie-break randomly."""
    soc = {"SoC-motiv-iso": SOC_MOTIV_ISO, "SoC1": SOCS["SoC1"]}[socname]
    sim = SoCSimulator(soc)
    env = vec.VecEnv.from_simulator(sim)
    app = _chain_app(soc, seed=3)
    compiled = vec.compile_app(app, soc, seed=TILE_SEED)
    cfg = qlearn.QConfig(decay_steps=compiled.n_steps * 2)
    mlp = socnn.init_mlp_qstate(jax.random.PRNGKey(7))
    for it in range(2):
        spec = vec.mlp_policy_spec(mlp, compiled.schedule)
        (_, mlp), _ = env.episode_spec(compiled, spec, cfg=cfg,
                                       key=jax.random.PRNGKey(it))
    mlp = socnn.freeze(mlp)
    pol = socnn.MLPQPolicy(mlp)
    des = sim.run(app, pol, seed=TILE_SEED, train=False)
    _, res = env.episode_spec(compiled, pol.lower(env, compiled))
    des_modes = [r.mode for p in des.phases for r in p.invocations]
    assert des_modes == [int(m) for m in np.asarray(res.mode)]
    dt = np.array([p.wall_time for p in des.phases])
    np.testing.assert_allclose(np.asarray(res.phase_time), dt, rtol=1e-4)


def test_serve_carries_and_trains_the_weights(nn_env):
    from repro.soc import traffic as traffic_mod

    soc, _, compiled, mlp = nn_env
    env = vec.VecEnv(soc, seed=0)
    senv = vec.ServeEnv(env, n_requests=64)
    tspec = traffic_mod.poisson(0.001, key=jax.random.PRNGKey(3))
    spec = vec.mlp_policy_spec(mlp, compiled.schedule)
    carry, _, sres = senv.serve(compiled, spec, tspec,
                                cfg=qlearn.QConfig(decay_steps=64),
                                key=jax.random.PRNGKey(1))
    assert int(sres.served) > 0
    assert bool(jnp.all(jnp.isfinite(carry.wpack)))
    assert bool(jnp.any(carry.wpack != mlp.wpack))
    # frozen network: served stream leaves the weights bitwise untouched
    fr = vec.mlp_policy_spec(socnn.freeze(mlp), compiled.schedule)
    carry_f, _, _ = senv.serve(compiled, fr, tspec,
                               key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(carry_f.wpack),
                                  np.asarray(mlp.wpack))


def test_stacked_lower_mlps_runs_k_by_b_grid():
    socs = [SOCS["SoC6"], SOCS["SoC2"]]
    from repro.soc import stacked as stk
    apps = [_chain_app(s, seed=i, n_phases=2) for i, s in enumerate(socs)]
    env = stk.StackedVecEnv(socs, seed=0)
    st = env.compile(apps)
    per_kb = [[socnn.init_mlp_qstate(jax.random.PRNGKey(k * 3 + b))
               for b in range(2)] for k in range(2)]
    mlps = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[jax.tree_util.tree_map(lambda *ys: jnp.stack(ys), *row)
          for row in per_kb])
    specs = env.lower_mlps(st, mlps)
    assert specs.mlp.wpack.shape[:2] == (2, 2)
    assert bool(jnp.all(specs.qfun)) and bool(jnp.all(specs.mlp.frozen))
    res = env.episodes(st, specs, qlearn.QConfig())
    assert np.isfinite(np.asarray(res.phase_time)).all()


# ------------------------------------------------------ portfolio training
def _portfolio_items(n=2):
    items = []
    for i, name in enumerate(("SoC6", "SoC2")[:n]):
        soc = SOCS[name]
        env = vec.VecEnv(soc, seed=0)
        comps = [vec.compile_app(_chain_app(soc, seed=10 + i, n_phases=2),
                                 soc, seed=TILE_SEED)]
        items.append((env, comps))
    return items


def test_train_portfolio_learns_a_shared_network():
    items = _portfolio_items()
    cfg = qlearn.QConfig(decay_steps=2048)
    mlp, hist = socnn.train_portfolio(items, cfg, iterations=3, batch=2,
                                      key=jax.random.PRNGKey(1))
    assert hist.shape == (3,) and np.isfinite(np.asarray(hist)).all()
    assert int(mlp.step) > 0
    assert bool(jnp.all(jnp.isfinite(mlp.wpack)))
    # the shared pack moved off the all-tie init
    fresh = socnn.init_mlp_qstate(jax.random.PRNGKey(99))
    dims = socnn.mlp_dims(mlp.cfg)
    x = jnp.linspace(0.1, 0.9, dims[0])
    row = socnn.forward_packed(mlp.wpack, x, dims)
    assert len(np.unique(np.asarray(row))) > 1
    del fresh


class _Killer:
    """Simulated crash: dies (before writing) after N successful saves."""

    def __init__(self, inner: CheckpointManager, die_after: int):
        self._inner, self._left = inner, die_after

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def save(self, step, tree):
        if self._left <= 0:
            raise KeyboardInterrupt("simulated crash")
        self._left -= 1
        return self._inner.save(step, tree)


def test_train_portfolio_checkpoint_resume_is_bitwise(tmp_path):
    """Crash after iteration 1's snapshot, resume from the manager: final
    weights, step counter and history equal the uninterrupted run (the
    per-iteration keys are fold_in-derived, never carried)."""
    cfg = qlearn.QConfig(decay_steps=2048)
    key = jax.random.PRNGKey(5)
    full, hist_full = socnn.train_portfolio(
        _portfolio_items(), cfg, iterations=3, batch=2, key=key)

    ckdir = str(tmp_path / "ck")
    with pytest.raises(KeyboardInterrupt):
        socnn.train_portfolio(
            _portfolio_items(), cfg, iterations=3, batch=2, key=key,
            manager=_Killer(CheckpointManager(ckdir, async_write=False), 1))
    mgr2 = CheckpointManager(ckdir, async_write=False)
    assert mgr2.latest_step() == 1
    resumed, hist_res = socnn.train_portfolio(
        _portfolio_items(), cfg, iterations=3, batch=2, key=key,
        manager=mgr2)
    np.testing.assert_array_equal(np.asarray(resumed.wpack),
                                  np.asarray(full.wpack))
    assert int(resumed.step) == int(full.step)
    np.testing.assert_array_equal(np.asarray(hist_res),
                                  np.asarray(hist_full))
