"""The simulator's own profiler spans and name scopes.

Every entry point marks its host preparation (``cohm.prep``) and the
call into its compiled program (``cohm.launch``) with profiler spans,
and its device programs name the pre-sampling (``cohm_presample``) and
the step (``cohm_step``).  These tests run each entry point small on the
CPU under ``jax.profiler.trace`` and read the host spans back from the
trace, and read the scopes from each lowered program's locations.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qlearn, rewards
from repro.core.modes import CoherenceMode
from repro.core.policies import FixedHomogeneous
from repro.soc import traffic, vecenv
from repro.soc.apps import make_phase
from repro.soc.config import SOC_MOTIV_ISO, SOC_MOTIV_PAR
from repro.soc.des import Application, SoCSimulator
from repro.soc.stacked import StackedVecEnv

SPANS = {"cohm.prep", "cohm.launch"}
SCOPES = ("cohm_step", "cohm_presample")
N_REQUESTS = 32


def _app(soc, seed):
    rng = np.random.default_rng(seed)
    return Application(name="spans", phases=[
        make_phase(rng, soc, name=f"p{i}", n_threads=2, size_classes=[c],
                   chain_len=2, loops=2) for i, c in enumerate(("S", "M"))])


def host_span_names(log_dir) -> list[str]:
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


class Small:
    """Two SoC lanes, two training iterations, two fixed policies per
    lane, one serving environment on the first lane."""

    def __init__(self):
        sims = [SoCSimulator(SOC_MOTIV_ISO, seed=1),
                SoCSimulator(SOC_MOTIV_PAR, seed=1)]
        self.env = StackedVecEnv.from_simulators(sims)
        apps = [_app(sim.soc, seed=5) for sim in sims]
        self.iters = [self.env.compile(apps, seed=it) for it in range(2)]
        self.cfg = qlearn.QConfig(decay_steps=jnp.asarray(
            [s * 2 for s in self.iters[0].n_steps], jnp.int32))
        self.weights = rewards.stack_weights(
            [rewards.PAPER_DEFAULT_WEIGHTS] * 2)
        self.keys = self.env._default_keys(self.env.n_lanes, 2)
        self.specs = self.env.lower(
            self.iters[0], [FixedHomogeneous(CoherenceMode.NON_COH_DMA),
                            FixedHomogeneous(CoherenceMode.COH_DMA)])
        self.tspec = traffic.poisson(1e-6, seed=3)
        lane = self.env.envs[0]
        self.compiled = vecenv.compile_app(apps[0], lane.soc, seed=0)
        self.serve_env = vecenv.ServeEnv(lane, n_requests=N_REQUESTS)
        self.serve_spec = lane.lower(self.compiled, "q")

    def train_batched(self):
        return self.env.train_batched(self.iters, self.cfg, self.weights,
                                      self.keys)

    def episodes(self):
        return self.env.episodes(self.iters[0], self.specs, self.cfg)

    def stacked_serve(self):
        return self.env.serve(self.iters[0], self.specs, self.tspec,
                              self.cfg, n_requests=N_REQUESTS)

    def serve_env_serve(self):
        return self.serve_env.serve(
            self.compiled, self.serve_spec,
            traffic.chunk_key(self.tspec, 1), key=jax.random.PRNGKey(2))


ENTRY_POINTS = ("train_batched", "episodes", "stacked_serve",
                "serve_env_serve")


@pytest.fixture(scope="module")
def small():
    s = Small()
    for name in ENTRY_POINTS:               # compile outside any trace
        jax.block_until_ready(getattr(s, name)())
    return s


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_entry_point_writes_prep_and_launch_spans(small, entry, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(getattr(small, entry)())
    names = host_span_names(tmp_path)
    assert SPANS <= set(names), sorted(set(names))
    assert names.index("cohm.prep") < names.index("cohm.launch")


def _lowered_text(cache, key, call) -> str:
    """Lower the jitted program ``cache[key]`` with the arguments one
    more call of the entry point hands it."""
    fn = cache[key]
    seen = []

    def spy(*args):
        seen.append(args)
        return fn(*args)

    cache[key] = spy
    try:
        jax.block_until_ready(call())
    finally:
        cache[key] = fn
    return fn.lower(*seen[0]).as_text(debug_info=True)


@pytest.mark.parametrize("entry,prefix", [("train_batched", "train_jit"),
                                          ("episodes", "episodes_jit"),
                                          ("stacked_serve", "serve_jit")])
def test_stacked_programs_name_their_scopes(small, entry, prefix):
    key = next(k for k in small.env._cache if k[0] == prefix)
    text = _lowered_text(small.env._cache, key, getattr(small, entry))
    for scope in SCOPES:
        assert re.search(rf'loc\("[^"]*\b{scope}\b', text), scope


def test_serve_env_program_names_its_scopes(small):
    cache = small.serve_env._serve_cache
    key = ("serve", N_REQUESTS)
    single, batched = cache[key]
    seen = []

    def spy(*args):
        seen.append(args)
        return single(*args)

    cache[key] = (spy, batched)
    try:
        jax.block_until_ready(small.serve_env_serve())
    finally:
        cache[key] = (single, batched)
    text = single.lower(*seen[0]).as_text(debug_info=True)
    for scope in SCOPES:
        assert re.search(rf'loc\("[^"]*\b{scope}\b', text), scope


def test_unfused_episode_names_its_presampling(small):
    lane = small.env.envs[0]
    ep = vecenv.build_episode_fn(small.compiled.n_phases,
                                 small.compiled.n_threads, lane.cycle_time,
                                 fused=False)
    spec = lane.lower(small.compiled, "q")
    text = jax.jit(ep).lower(
        lane.params, small.compiled.schedule, spec, qlearn.QConfig(),
        rewards.PAPER_DEFAULT_WEIGHTS, jax.random.PRNGKey(0)).as_text(
            debug_info=True)
    assert re.search(r'loc\("[^"]*\bcohm_presample\b', text)


_FOUR_DEVICE_SCRIPT = r"""
import glob, os, sys
import jax, jax.numpy as jnp, numpy as np
from jax.profiler import ProfileData
from repro.core import qlearn, rewards
from repro.soc import shard
from repro.soc.apps import make_phase
from repro.soc.config import SOC_MOTIV_ISO, SOC_MOTIV_PAR
from repro.soc.des import Application, SoCSimulator
from repro.soc.stacked import StackedVecEnv

assert jax.device_count() == 4, jax.devices()
sims = [SoCSimulator(soc, seed=1) for soc in (SOC_MOTIV_ISO, SOC_MOTIV_PAR)]
env = StackedVecEnv.from_simulators(sims)
apps = []
for sim in sims:
    rng = np.random.default_rng(5)
    apps.append(Application(name="spans", phases=[
        make_phase(rng, sim.soc, name="p0", n_threads=2, size_classes=["S"],
                   chain_len=2, loops=2)]))
its = [env.compile(apps, seed=0)]
cfg = qlearn.QConfig(decay_steps=jnp.asarray(its[0].n_steps, jnp.int32))
wb = rewards.stack_weights([rewards.PAPER_DEFAULT_WEIGHTS] * 4)
keys = env._default_keys(env.n_lanes, 4)
jax.block_until_ready(shard.sharded_train_batched_stacked(
    env, its, cfg, wb, keys))
with jax.profiler.trace(sys.argv[1]):
    jax.block_until_ready(shard.sharded_train_batched_stacked(
        env, its, cfg, wb, keys))
path = glob.glob(os.path.join(sys.argv[1], "**", "*.xplane.pb"),
                 recursive=True)[0]
names = sorted({e.name for p in ProfileData.from_file(path).planes
                if p.name.startswith("/host:")
                for ln in p.lines for e in ln.events
                if e.name.startswith("cohm.")})
print("SPANS", " ".join(names))
"""


def test_sharded_trainer_writes_spans_on_four_devices(tmp_path):
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_SCRIPT, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    line = next((ln for ln in p.stdout.splitlines()
                 if ln.startswith("SPANS")), None)
    assert line is not None, p.stderr[-3000:]
    assert SPANS <= set(line.split()[1:]), line
