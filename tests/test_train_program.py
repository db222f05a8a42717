"""Training's per-call preparation lives in its jitted program.

``StackedVecEnv.train_batched`` hands its program the iterations'
schedules and the raw (K, B, 2) keys; the program stacks the schedules
and builds the fresh agents and their ``TrainCarry``.  These tests check
that a warm call then traces and compiles nothing on the host, and that
the program's results equal, bit for bit, the formulation that prepared
those operands eagerly and passed them to the same vmapped
``build_train_fn``.
"""
import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qlearn, rewards
from repro.soc import stacked, vecenv
from repro.soc.apps import make_phase
from repro.soc.config import SOC_MOTIV_ISO, SOC_MOTIV_PAR
from repro.soc.des import Application, SoCSimulator
from repro.soc.stacked import StackedVecEnv

# Agents per lane: two lanes of FLAT // 2 fill whole FLAT_GRID_ROWS tiles
# (one flattened vmap), three agents leave the nested lane x agent vmaps.
AGENTS = {"flat": stacked.FLAT_GRID_ROWS // 2, "nested": 3}

# Events of a jaxpr trace and of a program's lowering, compilation or
# load from the persistent cache (as chipbench's CompileCounter counts).
TRACE = "/jax/core/compile/jaxpr_trace_duration"
BUILD = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
         "/jax/core/compile/backend_compile_duration",
         "/jax/compilation_cache/cache_retrieval_time_sec")


def _app(soc, seed):
    rng = np.random.default_rng(seed)
    return Application(name="train", phases=[
        make_phase(rng, soc, name=f"p{i}", n_threads=2, size_classes=[c],
                   chain_len=2, loops=2) for i, c in enumerate(("S", "M"))])


class Setup:
    """Two SoC lanes, B agents of two weightings, two iterations."""

    def __init__(self, grid: str, per_lane_decay: bool = True):
        sims = [SoCSimulator(soc, seed=1)
                for soc in (SOC_MOTIV_ISO, SOC_MOTIV_PAR)]
        self.env = StackedVecEnv.from_simulators(sims)
        apps = [_app(sim.soc, seed=5) for sim in sims]
        self.iters = [self.env.compile(apps, seed=it) for it in range(2)]
        self.eval_stacked = self.env.compile(apps, seed=9)
        decay = ([s * 2 for s in self.iters[0].n_steps] if per_lane_decay
                 else 2 * max(self.iters[0].n_steps))
        self.cfg = qlearn.QConfig(decay_steps=jnp.asarray(decay, jnp.int32))
        b = AGENTS[grid]
        pair = [rewards.PAPER_DEFAULT_WEIGHTS,
                rewards.RewardWeights(0.5, 0.25, 0.25)]
        self.weights = rewards.stack_weights((pair * b)[:b])
        self.keys = self.env._default_keys(self.env.n_lanes, b)

    def call(self, eval_stacked=None):
        return self.env.train_batched(self.iters, self.cfg, self.weights,
                                      self.keys, eval_stacked)

    def eager(self, eval_stacked=None):
        """The operands prepared eagerly on the host, passed to the same
        vmapped train function the call's program builds."""
        env, first = self.env, self.iters[0]
        k, b = self.keys.shape[:2]
        scheds = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=1),
            *[st.schedule for st in self.iters])
        q0 = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (k,) + x.shape),
            jax.vmap(lambda _: qlearn.init_qstate(qlearn.QConfig()))(
                jnp.arange(b)))
        carry_axes = vecenv.TrainCarry(key=0, it=None, best=0)
        carry0 = vecenv.TrainCarry(
            key=jnp.asarray(self.keys), it=jnp.zeros((), jnp.int32),
            best=jnp.full((k, b), -jnp.inf, jnp.float32))
        if eval_stacked is None:
            eval_shape = eval_sched = base = pmask = None
            eval_axes = (None, None, None)
        else:
            eval_shape = (eval_stacked.n_phases, eval_stacked.n_threads)
            eval_sched = eval_stacked.schedule
            base = env.baseline(eval_stacked)
            pmask = eval_stacked.phase_mask
            eval_axes = (0, 0, 0)
        axes = stacked._cfg_axes(self.cfg)
        train_one = vecenv.build_train_fn(
            first.n_phases, first.n_threads, eval_shape, env.cycle_time,
            demand_cache=True, gated=True, fused=env.fused_step,
            kernel=vecenv.episode_lowering(env.calls, k * b,
                                           env.fused_step))
        grid = jax.jit(stacked._vmap_grid(
            train_one,
            lane_axes=(0, 0, *eval_axes, axes, None, carry_axes, 0, None),
            item_axes=(None, None, None, None, None, None,
                       rewards.RewardWeights(0, 0, 0), carry_axes, 0, None),
            out_axes=(0, carry_axes, 0)))
        qs, _, hist = grid(env.params, scheds, eval_sched, base, pmask,
                           self.cfg, self.weights, carry0, q0, None)
        return qs, hist


@pytest.fixture
def events():
    """Names of the duration events JAX records while the test runs."""
    seen = []

    def listener(event, duration, *_a, **_k):
        seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listener)


@pytest.mark.parametrize("grid", sorted(AGENTS))
def test_warm_train_call_traces_and_compiles_nothing(grid, events):
    s = Setup(grid)
    jax.block_until_ready(s.call())
    events.clear()
    jax.block_until_ready(s.call())
    assert events.count(TRACE) == 0, events
    assert not [e for e in events if e in BUILD], events


@pytest.mark.parametrize("evaluate", [False, True],
                         ids=["no-eval", "eval"])
@pytest.mark.parametrize("per_lane_decay", [True, False],
                         ids=["lane-decay", "shared-decay"])
@pytest.mark.parametrize("grid", sorted(AGENTS))
def test_program_matches_eager_preparation(grid, per_lane_decay, evaluate):
    s = Setup(grid, per_lane_decay)
    ev = s.eval_stacked if evaluate else None
    got_q, got_hist = s.call(ev)
    want_q, want_hist = s.eager(ev)
    for field in qlearn.QState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got_q, field)),
                                      np.asarray(getattr(want_q, field)),
                                      err_msg=field)
    assert got_q.qtable.shape[:2] == s.keys.shape[:2]
    for got, want in zip(jax.tree_util.tree_leaves(got_hist),
                         jax.tree_util.tree_leaves(want_hist)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if evaluate:
        assert np.asarray(got_hist[0]).shape == s.keys.shape[:2] + (2,)
