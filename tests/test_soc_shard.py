"""repro.soc.shard: the shard_map scale-out must not change results.

On a 1-device host the default path falls back to the plain vmap call —
bitwise-identical by construction, pinned here — while
``force_shard_map=True`` exercises the real shard_map wrapper on a
single-device lane mesh: integer state (visits, step counters, modes)
stays bitwise and float leaves agree to roundoff (the wrapper re-jits
the program, so XLA may refuse reductions in a different order).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qlearn, rewards
from repro.soc import shard, vecenv
from repro.soc.apps import make_phase
from repro.soc.config import SOC_MOTIV_ISO, SOC_MOTIV_PAR
from repro.soc.des import Application, SoCSimulator
from repro.soc.stacked import StackedVecEnv


def _chain_app(soc, seed, n_threads=2):
    rng = np.random.default_rng(seed)
    phases = [make_phase(rng, soc, name=f"p{i}", n_threads=n_threads,
                         size_classes=[c], chain_len=2, loops=2)
              for i, c in enumerate(("S", "M"))]
    return Application(name=f"{soc.name}-shard-test", phases=phases)


def _tree_bitwise(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _tree_close(a, b, rtol=1e-5, atol=1e-6):
    """Integer leaves bitwise, float leaves to roundoff."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)
        else:
            np.testing.assert_array_equal(x, y)


def test_lane_mesh_covers_all_devices():
    mesh = shard.lane_mesh()
    assert mesh.axis_names == ("lanes",)
    assert int(mesh.devices.size) == jax.device_count()


# ----------------------------------------------------------- VecEnv (B) ----
@pytest.fixture(scope="module")
def vec_setup():
    soc = SOC_MOTIV_PAR
    env = vecenv.VecEnv(soc, seed=0)
    app = _chain_app(soc, seed=4)
    compiled = vecenv.compile_app(app, soc, seed=7)
    iters, B = 2, 4
    cfg = qlearn.QConfig(decay_steps=compiled.n_steps * iters)
    wb = rewards.stack_weights([rewards.PAPER_DEFAULT_WEIGHTS] * B)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B))
    return env, [compiled] * iters, cfg, wb, keys


def test_train_batched_default_fallback_bitwise(vec_setup):
    env, apps, cfg, wb, keys = vec_setup
    direct = env.train_batched(apps, cfg, wb, keys)
    via = shard.sharded_train_batched(env, apps, cfg, wb, keys)
    _tree_bitwise(direct, via)


def test_train_batched_forced_shard_map(vec_setup):
    env, apps, cfg, wb, keys = vec_setup
    qs, _ = env.train_batched(apps, cfg, wb, keys)
    qs_s, _ = shard.sharded_train_batched(env, apps, cfg, wb, keys,
                                          force_shard_map=True)
    _tree_close(qs, qs_s)
    # integer Q-state leaves must stay exactly equal even under shard_map
    np.testing.assert_array_equal(np.asarray(qs.visits),
                                  np.asarray(qs_s.visits))
    np.testing.assert_array_equal(np.asarray(qs.step),
                                  np.asarray(qs_s.step))


# --------------------------------------------------- StackedVecEnv (K, B) ----
@pytest.fixture(scope="module")
def stacked_setup():
    sims = [SoCSimulator(SOC_MOTIV_ISO, seed=1),
            SoCSimulator(SOC_MOTIV_PAR, seed=1)]
    env = StackedVecEnv.from_simulators(sims)
    apps = [_chain_app(sim.soc, seed=5) for sim in sims]
    iters, B = 2, 4
    stacked_iters = [env.compile(apps, seed=it) for it in range(iters)]
    cfg = qlearn.QConfig(decay_steps=jnp.asarray(
        [s * iters for s in stacked_iters[0].n_steps], jnp.int32))
    wb = rewards.stack_weights([rewards.PAPER_DEFAULT_WEIGHTS] * B)
    keys = env._default_keys(env.n_lanes, B)
    return env, stacked_iters, cfg, wb, keys


def test_stacked_train_batched_fallback_bitwise(stacked_setup):
    env, its, cfg, wb, keys = stacked_setup
    direct = env.train_batched(its, cfg, wb, keys)
    via = shard.sharded_train_batched_stacked(env, its, cfg, wb, keys)
    _tree_bitwise(direct, via)


def test_stacked_train_batched_forced_shard_map(stacked_setup):
    env, its, cfg, wb, keys = stacked_setup
    qs, _ = env.train_batched(its, cfg, wb, keys)
    qs_s, _ = shard.sharded_train_batched_stacked(env, its, cfg, wb, keys,
                                                  force_shard_map=True)
    _tree_close(qs, qs_s)


def test_episodes_fallback_bitwise_and_forced_close(stacked_setup):
    env, its, cfg, wb, keys = stacked_setup
    stacked = its[0]
    qs, _ = env.train_batched(its, cfg, wb, keys)
    specs = env.lower_qstates(stacked, qs, freeze=True)
    ekeys = env._default_keys(*specs.learned.shape)
    direct = env.episodes(stacked, specs, cfg, ekeys)
    via = shard.sharded_episodes(env, stacked, specs, cfg, ekeys)
    _tree_bitwise(direct, via)
    forced = shard.sharded_episodes(env, stacked, specs, cfg, ekeys,
                                    force_shard_map=True)
    np.testing.assert_array_equal(np.asarray(direct.mode),
                                  np.asarray(forced.mode))
    np.testing.assert_array_equal(np.asarray(direct.state_idx),
                                  np.asarray(forced.state_idx))
    _tree_close(direct, forced)


# ------------------------------------------------ four virtual devices ----
# A subprocess gets four CPU devices (the main pytest process keeps its
# one-device view): the lane-sharded trainer must split the agents over
# all four and match the single-device vmap call.
_FOUR_DEVICE_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import qlearn, rewards
from repro.soc import shard
from repro.soc.apps import make_phase
from repro.soc.config import SOC_MOTIV_ISO, SOC_MOTIV_PAR
from repro.soc.des import Application, SoCSimulator
from repro.soc.stacked import StackedVecEnv

assert jax.device_count() == 4, jax.devices()
sims = [SoCSimulator(SOC_MOTIV_ISO, seed=1), SoCSimulator(SOC_MOTIV_PAR, seed=1)]
env = StackedVecEnv.from_simulators(sims)
apps = []
for sim in sims:
    rng = np.random.default_rng(5)
    apps.append(Application(name="four-device", phases=[
        make_phase(rng, sim.soc, name=f"p{i}", n_threads=2, size_classes=[c],
                   chain_len=2, loops=2) for i, c in enumerate(("S", "M"))]))
its = [env.compile(apps, seed=it) for it in range(2)]
cfg = qlearn.QConfig(decay_steps=jnp.asarray(
    [s * 2 for s in its[0].n_steps], jnp.int32))

def agents(b):
    return (rewards.stack_weights([rewards.PAPER_DEFAULT_WEIGHTS] * b),
            env._default_keys(env.n_lanes, b))

wb, keys = agents(8)
qs4, _ = shard.sharded_train_batched_stacked(env, its, cfg, wb, keys)
qs1, _ = env.train_batched(its, cfg, wb, keys)
for a, b in zip(jax.tree_util.tree_leaves(qs4), jax.tree_util.tree_leaves(qs1)):
    a, b = np.asarray(a), np.asarray(b)
    if np.issubdtype(a.dtype, np.floating):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    else:
        np.testing.assert_array_equal(a, b)
sh = qs4.qtable.sharding
assert len(sh.device_set) == 4 and not sh.is_fully_replicated, sh
print("SHARDED_OK")
try:
    shard.sharded_train_batched_stacked(env, its, cfg, *agents(6))
except ValueError as e:
    print("RAISES_OK", e)

import json
from repro.soc import dse
out = dse.run_sweep(dse.sample_socs(13, 3), iters=2, n_phases=2,
                    max_buckets=1)
print("SWEEP_OK", json.dumps(np.asarray(out["norm_time"]).tolist()))
"""


@pytest.fixture(scope="module")
def four_device_run():
    import os
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_stacked_train_sharded_over_four_devices(four_device_run):
    assert "SHARDED_OK" in four_device_run.stdout, (
        four_device_run.stderr[-3000:])


def test_batch_not_dividing_multi_device_mesh_raises(four_device_run):
    assert "RAISES_OK" in four_device_run.stdout, (
        four_device_run.stderr[-3000:])
    assert "does not divide over the 4-device mesh" in four_device_run.stdout


def test_run_sweep_on_four_devices_matches_one(four_device_run):
    """``dse.run_sweep`` trains one agent per lane unsharded, so a host
    with four devices must give exactly the one-device sweep."""
    import json

    from repro.soc import dse

    line = next((ln for ln in four_device_run.stdout.splitlines()
                 if ln.startswith("SWEEP_OK ")), None)
    assert line is not None, four_device_run.stderr[-3000:]
    got = np.asarray(json.loads(line[len("SWEEP_OK "):]))
    want = dse.run_sweep(dse.sample_socs(13, 3), iters=2, n_phases=2,
                         max_buckets=1)["norm_time"]
    np.testing.assert_array_equal(got, np.asarray(want, got.dtype))
