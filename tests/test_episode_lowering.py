"""Which lowering the batched entry points give the fused episode step.

A call that runs ``ops.SCAN_MIN_EPISODES`` or more episodes at once
lowers them through the XLA scan (``ref.episode_ref``) on every platform;
a smaller call keeps the platform's default, the Pallas kernel on a TPU.
The tests make the CPU host stand in for an accelerator (``ops._on_cpu``
answers False), record each entry point's jitted program in place of
running it, and lower the program for a TPU, which needs no chip: a
kernel shows as a ``tpu_custom_call``.  They also count the batching
passes over scan bodies while the batched programs trace (nested vmaps
and a constant initial carry each made the step body batch again), and
check a (lanes x items) grid run as one flattened vmap against nested
vmaps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.interpreters import batching

from repro.core import qlearn, rewards
from repro.core.modes import CoherenceMode
from repro.core.policies import FixedHomogeneous
from repro.kernels.soc_step import ops
from repro.soc import stacked, vecenv
from repro.soc.apps import make_phase
from repro.soc.config import SOC_MOTIV_ISO, SOC_MOTIV_PAR
from repro.soc.des import Application, SoCSimulator
from repro.soc.stacked import StackedVecEnv

CUT = ops.SCAN_MIN_EPISODES
FLAT = stacked.FLAT_GRID_ROWS


def _app(soc, seed):
    rng = np.random.default_rng(seed)
    return Application(name="lowering", phases=[
        make_phase(rng, soc, name=f"p{i}", n_threads=2, size_classes=[c],
                   chain_len=2, loops=2) for i, c in enumerate(("S", "M"))])


@pytest.fixture
def recorded(monkeypatch):
    """Entry-point calls trace their jitted program into this list and get
    its abstract result back; the host stands in for an accelerator."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    seen = []
    real_jit = jax.jit

    def recording_jit(fn, *a, **k):
        jitted = real_jit(fn, *a, **k)

        def call(*args):
            traced = jitted.trace(*args)
            seen.append(traced)
            return traced.out_info
        return call

    monkeypatch.setattr(jax, "jit", recording_jit)
    return seen


def _stacked_call(verb, lanes, n):
    sims = [SoCSimulator(soc, seed=1)
            for soc in (SOC_MOTIV_ISO, SOC_MOTIV_PAR)[:lanes]]
    env = StackedVecEnv.from_simulators(sims)
    apps = [_app(sim.soc, seed=5) for sim in sims]
    iters = [env.compile(apps, seed=it) for it in range(2)]
    cfg = qlearn.QConfig(decay_steps=jnp.asarray(
        [s * 2 for s in iters[0].n_steps], jnp.int32))
    if verb == "train":
        weights = rewards.stack_weights([rewards.PAPER_DEFAULT_WEIGHTS] * n)
        call = lambda: env.train_batched(iters, cfg, weights,
                                         env._default_keys(lanes, n))
    else:
        specs = env.lower(iters[0], [FixedHomogeneous(CoherenceMode(m % 4))
                                     for m in range(n)])
        call = lambda: env.episodes(iters[0], specs, cfg)
    return env, call


def _single_call(verb, n):
    lane = vecenv.VecEnv(SOC_MOTIV_ISO, seed=1)
    apps = [vecenv.compile_app(_app(SOC_MOTIV_ISO, seed=5), SOC_MOTIV_ISO,
                               seed=it) for it in range(2)]
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    if verb == "train":
        weights = rewards.stack_weights([rewards.PAPER_DEFAULT_WEIGHTS] * n)
        call = lambda: lane.train_batched(apps, qlearn.QConfig(), weights,
                                          keys)
    else:
        specs = vecenv.stack_specs([lane.lower(apps[0], "fixed",
                                               fixed_modes=m % 4)
                                    for m in range(n)])
        call = lambda: lane.episodes(apps[0], specs, keys=keys)
    return lane, call


# Calls below the crossover, at it, and with a (lanes x items) grid that
# fills whole FLAT_GRID_ROWS tiles, which runs as one flattened vmap.
CALLS = {
    "stacked-small": lambda verb: _stacked_call(verb, 1, CUT - 1),
    "stacked-batched": lambda verb: _stacked_call(verb, 2, -(-CUT // 2)),
    "stacked-flat": lambda verb: _stacked_call(verb, 2, FLAT // 2),
    "single-small": lambda verb: _single_call(verb, CUT - 1),
    "single-batched": lambda verb: _single_call(verb, CUT),
}


@pytest.mark.parametrize("verb", ["train", "eval"])
@pytest.mark.parametrize("which", sorted(CALLS))
def test_lowering_follows_episode_count(recorded, which, verb):
    env, call = CALLS[which](verb)
    call()
    (traced,) = recorded
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    kernel = which.endswith("small")
    assert ("tpu_custom_call" in text) == kernel
    taken = "episode_kernel" if kernel else "episode_scan"
    other = "episode_scan" if kernel else "episode_kernel"
    assert env.calls[taken] == 1 and env.calls[other] == 0


@pytest.mark.parametrize("which,verb,passes", [
    ("stacked-batched", "train", 4), ("stacked-batched", "eval", 2),
    ("stacked-flat", "train", 2), ("stacked-flat", "eval", 1),
    ("single-batched", "train", 2), ("single-batched", "eval", 1)])
def test_scan_bodies_batch_once_per_level(recorded, monkeypatch, which,
                                          verb, passes):
    """Tracing a batched program batches each scan body once per vmap
    level: training's iteration scan and the step scan inside it,
    evaluation's step scan; nested lane and item vmaps are two levels, a
    flattened grid or a single SoC one.  A constant initial slot table
    and reward extrema made the scan's batching rule batch the step body
    a second time at each level."""
    count = [0]
    real = batching.batch_jaxpr

    def counted(*a, **k):
        count[0] += 1
        return real(*a, **k)

    monkeypatch.setattr(batching, "batch_jaxpr", counted)
    _, call = CALLS[which](verb)
    call()
    assert len(recorded) == 1
    assert count[0] == passes


@pytest.mark.parametrize("verb", ["train", "eval"])
def test_flat_grid_matches_nested_vmaps(monkeypatch, verb):
    """A grid run as one flattened vmap gives what nested vmaps give."""
    _, call = _stacked_call(verb, 2, FLAT // 2)
    flat = jax.tree_util.tree_map(np.asarray, call())
    monkeypatch.setattr(stacked, "FLAT_GRID_ROWS", 10 ** 9)
    _, call = _stacked_call(verb, 2, FLAT // 2)
    nested = jax.tree_util.tree_map(np.asarray, call())
    for a, b in zip(jax.tree_util.tree_leaves(flat),
                    jax.tree_util.tree_leaves(nested)):
        np.testing.assert_array_equal(a, b)
