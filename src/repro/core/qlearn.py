"""Tabular Q-learning agent (paper §4.2).

Faithful to the paper's formulation:

  * Q-table of |S| x |A| = 243 x 4 = 972 entries, zero-initialized.
  * epsilon-greedy action selection (explore with prob. epsilon, otherwise
    argmax over the Q-row for the sensed state).
  * Update rule ``Q(s,a) <- (1-alpha) Q(s,a) + alpha R(s,a)`` — note the
    paper uses the immediate multi-objective reward with no bootstrapped
    ``max_a' Q(s',a')`` term (a contextual-bandit-style update), which we
    keep exactly.
  * epsilon (init 0.5) and alpha (init 0.25) decay **linearly to zero** over
    a configured number of training iterations (paper §5 Experimental
    Setup); after convergence updates are disabled and the greedy policy is
    evaluated.

Everything is a pure function over a :class:`QState` pytree, so training can
run under ``jit``/``lax.scan`` and thousands of agents can be trained in
parallel with ``vmap`` (used by the Fig. 6 reward-DSE benchmark).

Action masking: per the paper, "COHMELEON does not necessarily require
support for all four coherence modes; it makes the selection based on the
options that are available" — ``select`` takes an ``action_mask``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.modes import CoherenceMode, N_MODES
from repro.core.state import N_STATES
from repro.core.vops import iota

# numpy so it inlines as a literal under Pallas tracing
_NEG = np.float32(-3.4e38)
# The degradation-safe fallback action: always available by construction.
_FALLBACK = int(CoherenceMode.NON_COH_DMA)


class QConfig(NamedTuple):
    n_states: int = N_STATES
    n_actions: int = N_MODES
    epsilon0: float = 0.5     # paper initialization
    alpha0: float = 0.25      # paper initialization
    decay_steps: int = 3000   # invocations until eps/alpha hit zero
    # Beyond-paper robustness fix (EXPERIMENTS.md §Paper-validation): the
    # paper zero-initializes Q; with a noisy multi-objective reward an
    # epsilon-greedy agent can freeze a bad arm off 2-3 early samples
    # (alpha decays) and self-reinforce.  Optimistic init at the reward
    # upper bound makes every arm get pulled while alpha is still large.
    # An untrained table is all-ties -> uniform random, preserving the
    # paper's "iteration 0 == Random policy" property (Fig. 8).
    q_init: float = 1.0
    # Reward-collapse watchdog (fault robustness, :func:`reward_watchdog`):
    # if an episode's mean reward drops below ``collapse_frac`` of the best
    # episode seen so far while still training, the decay counter is wound
    # back so epsilon/alpha re-open to ``reopen_frac`` of their initial
    # values — a degraded SoC invalidates the learned table and the agent
    # must re-explore.  ``collapse_frac = 0`` disables the watchdog (the
    # default; the training scan is then bitwise-identical to the
    # watchdog-free program).
    collapse_frac: float = 0.0
    reopen_frac: float = 0.5


class QState(NamedTuple):
    qtable: jnp.ndarray   # (S, A) float32
    visits: jnp.ndarray   # (S, A) int32 — diagnostics / breakdown plots
    step: jnp.ndarray     # () int32, training invocations so far
    frozen: jnp.ndarray   # () bool — True once training is disabled


def init_qstate(cfg: QConfig = QConfig()) -> QState:
    return QState(
        qtable=jnp.full((cfg.n_states, cfg.n_actions), cfg.q_init,
                        jnp.float32),
        visits=jnp.zeros((cfg.n_states, cfg.n_actions), jnp.int32),
        step=jnp.zeros((), jnp.int32),
        frozen=jnp.zeros((), bool),
    )


def schedule(cfg: QConfig, step):
    """Linearly decayed (epsilon, alpha) at ``step``."""
    frac = jnp.clip(1.0 - step.astype(jnp.float32) / cfg.decay_steps, 0.0, 1.0)
    return cfg.epsilon0 * frac, cfg.alpha0 * frac


def select(
    qs: QState,
    cfg: QConfig,
    state_idx,
    key,
    action_mask=None,
):
    """Epsilon-greedy action for ``state_idx``. Returns int32 action."""
    if action_mask is None:
        action_mask = jnp.ones((cfg.n_actions,), bool)
    eps, _ = schedule(cfg, qs.step)
    eps = jnp.where(qs.frozen, 0.0, eps)

    k_explore, k_pick, k_tie = jax.random.split(key, 3)
    raw = qs.qtable[state_idx]
    row = jnp.where(action_mask, raw, _NEG)
    # Randomized argmax: ties (e.g. the all-zero row of an unvisited
    # state) break uniformly, so an untrained table == the Random policy
    # (paper Fig. 8, "iteration 0") instead of defaulting to action 0.
    is_max = row >= jnp.max(row) - 1e-9
    tie_logits = jnp.where(is_max & action_mask, 0.0, _NEG)
    greedy = jax.random.categorical(k_tie, tie_logits).astype(jnp.int32)

    logits = jnp.where(action_mask, 0.0, _NEG)
    random_action = jax.random.categorical(k_pick, logits).astype(jnp.int32)

    explore = jax.random.uniform(k_explore) < eps
    choice = jnp.where(explore, random_action, greedy)
    # Degradation safety: a corrupted (non-finite) Q-row falls back to the
    # always-available non-coherent mode instead of argmaxing over NaNs.
    return jnp.where(jnp.all(jnp.isfinite(raw)), choice, _FALLBACK)


class SelectNoise(NamedTuple):
    """Pre-sampled randomness for :func:`select_presampled`.

    ``select`` draws three independent variates per call (explore uniform,
    random-action gumbels, tie-break gumbels).  Inside a ``lax.scan`` the
    per-step ``split`` + ``categorical`` threefry calls dominate the step
    cost; pre-sampling the whole episode's noise in one batched call
    (:func:`sample_select_noise`) and feeding rows through the scan xs
    keeps the per-step work at two argmaxes and a compare."""

    u_explore: jnp.ndarray   # (..., ) uniform [0, 1)
    g_pick: jnp.ndarray      # (..., A) gumbel — uniform-random action draw
    g_tie: jnp.ndarray       # (..., A) gumbel — randomized-argmax tie-break


def sample_select_noise(key, shape_prefix: tuple,
                        n_actions: int = N_MODES) -> SelectNoise:
    """One batched threefry call's worth of select noise for ``shape_prefix``
    steps (e.g. ``(S,)`` for an episode of S invocations)."""
    k_explore, k_pick, k_tie = jax.random.split(key, 3)
    return SelectNoise(
        u_explore=jax.random.uniform(k_explore, shape_prefix),
        g_pick=jax.random.gumbel(k_pick, (*shape_prefix, n_actions)),
        g_tie=jax.random.gumbel(k_tie, (*shape_prefix, n_actions)),
    )


def select_presampled(
    qs: QState,
    cfg: QConfig,
    state_idx,
    noise: SelectNoise,
    action_mask=None,
):
    """:func:`select` with the randomness supplied as one :class:`SelectNoise`
    row.  Identical distribution — ``categorical(key, logits)`` is
    ``argmax(logits + gumbel)``, which is what this computes — but with no
    per-call threefry, so it is the hot-path variant used inside the
    vectorized environment's scan step."""
    if action_mask is None:
        action_mask = jnp.ones((cfg.n_actions,), bool)
    eps, _ = schedule(cfg, qs.step)
    eps = jnp.where(qs.frozen, 0.0, eps)

    raw = qs.qtable[state_idx]
    row = jnp.where(action_mask, raw, _NEG)
    is_max = row >= jnp.max(row) - 1e-9
    tie_logits = jnp.where(is_max & action_mask, 0.0, _NEG)
    greedy = jnp.argmax(tie_logits + noise.g_tie, axis=-1).astype(jnp.int32)

    logits = jnp.where(action_mask, 0.0, _NEG)
    random_action = jnp.argmax(logits + noise.g_pick,
                               axis=-1).astype(jnp.int32)

    explore = noise.u_explore < eps
    choice = jnp.where(explore, random_action, greedy)
    # Same non-finite-row fallback as `select`/`row_select_presampled`.
    return jnp.where(jnp.all(jnp.isfinite(raw)), choice, _FALLBACK)


def row_select_presampled(row, eps, noise: SelectNoise, action_mask):
    """:func:`select_presampled` on a pre-gathered Q-row with a precomputed
    epsilon.

    The fused episode step gathers ``qtable[state_idx]`` once and feeds the
    same row to selection and to :func:`row_update`, and precomputes the
    whole episode's (epsilon, alpha) decay outside the scan
    (:func:`decay_arrays`) — this is the selection half.  Identical floats
    to ``select_presampled`` (same masked row, same gumbel argmaxes)."""
    mrow = jnp.where(action_mask, row, _NEG)
    is_max = mrow >= jnp.max(mrow) - 1e-9
    tie_logits = jnp.where(is_max & action_mask, 0.0, _NEG)
    greedy = jnp.argmax(tie_logits + noise.g_tie, axis=-1).astype(jnp.int32)
    logits = jnp.where(action_mask, 0.0, _NEG)
    random_action = jnp.argmax(logits + noise.g_pick,
                               axis=-1).astype(jnp.int32)
    choice = jnp.where(noise.u_explore < eps, random_action, greedy)
    # Same non-finite-row fallback as `select`/`select_presampled`; on a
    # finite row the select is bitwise-free (where(True, choice, _) on
    # exact integers).
    return jnp.where(jnp.all(jnp.isfinite(row)), choice, _FALLBACK)


def row_update(row, alpha, action, reward):
    """The paper update on a pre-gathered Q-row: the blended row to write
    back with ``qtable.at[state_idx].set``.  ``alpha == 0`` (frozen, or a
    decayed-to-zero schedule) leaves the row bitwise unchanged.

    Degradation safety: a non-finite reward (a fault-corrupted timing
    model, a poisoned extrema table) must never reach the blend — both
    alpha and the reward are zeroed (zeroing alpha alone still leaks
    ``0 * NaN == NaN`` into the row) so the row stays intact.  On finite
    rewards the guards are ``where(True, x, 0)``, exact no-ops."""
    ok = jnp.isfinite(reward)
    alpha = jnp.where(ok, alpha, 0.0)
    reward = jnp.where(ok, reward, 0.0)
    hot = iota(row.shape[-1]) == action
    return jnp.where(hot, (1.0 - alpha) * row + alpha * reward, row)


def decay_arrays(cfg: QConfig, step0, frozen, inc):
    """Per-step ``(eps_t, alpha_t)`` for an episode, precomputed outside the
    scan.

    ``inc`` is the (S,) int32 per-step counter increment the in-scan update
    would apply (``valid & ~frozen`` — zero on frozen agents and on stacked
    padding rows), so step ``i`` sees the counter value
    ``step0 + sum(inc[:i])`` — exactly the carried ``qs.step`` the unfused
    step reads.  Same float formula as :func:`schedule`, so the values are
    bitwise-identical to the in-scan ones."""
    inc = inc.astype(jnp.int32)
    step_t = step0 + jnp.cumsum(inc) - inc          # counter BEFORE step i
    frac = jnp.clip(1.0 - step_t.astype(jnp.float32) / cfg.decay_steps,
                    0.0, 1.0)
    eps_t = jnp.where(frozen, 0.0, cfg.epsilon0 * frac)
    alpha_t = jnp.where(frozen, 0.0, cfg.alpha0 * frac)
    return eps_t, alpha_t


def replay_visits(qs0: QState, qtable, state_idx, action, inc) -> QState:
    """Rebuild the post-episode :class:`QState` from the trained table plus
    the episode trace, reconstructing ``visits``/``step`` with one batched
    scatter-add.

    In-scan accumulation adds ``inc`` at ``(state_idx, action)`` every step;
    integer addition commutes, so a single
    ``visits.at[state_idx, action].add(inc)`` over the whole trace is
    bitwise-equal — and it takes the (S, A) visits table out of the scan
    carry entirely (the fused step carries only the Q-table)."""
    inc = inc.astype(jnp.int32)
    return QState(
        qtable=qtable,
        visits=qs0.visits.at[state_idx, action].add(inc),
        step=qs0.step + jnp.sum(inc),
        frozen=qs0.frozen,
    )


def update(qs: QState, cfg: QConfig, state_idx, action, reward,
           debug_finite: bool = False) -> QState:
    """Paper update: Q(s,a) <- (1-alpha) Q(s,a) + alpha R(s,a).

    Written as row gather -> one-hot blend -> row write-back rather than a
    ``.at[state_idx, action]`` scatter: XLA keeps a single-dynamic-index
    row update in place inside ``lax.scan``, while the two-dynamic-index
    scatter falls off the in-place path and dominates the whole training
    step (measured ~20x slower in the vectorized environment's scan).
    The arithmetic on the updated element is unchanged.

    A non-finite reward is dropped (:func:`row_update`'s guard): the table
    stays intact and only the visit/step counters advance.  With
    ``debug_finite=True`` the step additionally host-checks the incoming
    reward and the written row (:func:`debug_finite_check`) — a debugging
    aid, off by default so the hot path carries no callback."""
    _, alpha = schedule(cfg, qs.step)
    alpha = jnp.where(qs.frozen, 0.0, alpha)
    row = qs.qtable[state_idx]
    new_row = row_update(row, alpha, action, reward)
    if debug_finite:
        debug_finite_check("qlearn.update", reward=reward, qrow=new_row)
    hot = jnp.arange(row.shape[-1], dtype=jnp.int32) == action
    inc = jnp.where(qs.frozen, 0, 1).astype(jnp.int32)
    new_vrow = qs.visits[state_idx] + hot.astype(jnp.int32) * inc
    return QState(
        qtable=qs.qtable.at[state_idx].set(new_row),
        visits=qs.visits.at[state_idx].set(new_vrow),
        step=qs.step + inc,
        frozen=qs.frozen,
    )


def episode_step(
    qs: QState,
    cfg: QConfig,
    state_idx,
    key,
    reward_fn,
    action_mask=None,
):
    """One sense->select->act->evaluate->update cycle as a pure function.

    ``reward_fn(action) -> (reward, aux)`` is the environment half of the
    step (timing model + reward evaluation); everything nests under
    ``jit``/``lax.scan``/``vmap``.  A frozen ``qs`` makes the update a
    no-op, so the same step serves training and greedy evaluation.  This is
    the episode-step used by the vectorized environment (``soc.vecenv``).

    Returns ``(new_qs, (action, reward, aux))``.
    """
    action = select(qs, cfg, state_idx, key, action_mask)
    reward, aux = reward_fn(action)
    new_qs = update(qs, cfg, state_idx, action, reward)
    return new_qs, (action, reward, aux)


def episode_step_presampled(
    qs: QState,
    cfg: QConfig,
    state_idx,
    noise: SelectNoise,
    reward_fn,
    action_mask=None,
):
    """:func:`episode_step` with pre-sampled select noise (the variant the
    vectorized environment scans with — see :class:`SelectNoise`)."""
    action = select_presampled(qs, cfg, state_idx, noise, action_mask)
    reward, aux = reward_fn(action)
    new_qs = update(qs, cfg, state_idx, action, reward)
    return new_qs, (action, reward, aux)


def init_qstate_batch(cfg: QConfig, batch: int) -> QState:
    """``batch`` independent agents as one stacked QState pytree (vmap axis 0)."""
    return jax.vmap(lambda _: init_qstate(cfg))(jnp.arange(batch))


def freeze(qs: QState) -> QState:
    """Disable further updates (paper: evaluate the converged model)."""
    return qs._replace(frozen=jnp.ones((), bool))


def frozen_qstate(cfg: QConfig = QConfig()) -> QState:
    """A frozen, untrained table.

    Two distinct uses share this shape: the Random policy's lowering (an
    all-ties table under randomized argmax picks uniformly over available
    modes) and the inert placeholder agent a non-learned
    :class:`~repro.soc.vecenv.PolicySpec` carries — frozen means the
    unified episode's update is a bitwise no-op, so fixed/manual specs need
    no Q-branch of their own."""
    return freeze(init_qstate(cfg))


def greedy_policy(qs: QState) -> jnp.ndarray:
    """(S,) argmax table — the learned coherence-selection policy."""
    return jnp.argmax(qs.qtable, axis=-1).astype(jnp.int32)


def reopen_step(cfg: QConfig, step):
    """The decay-counter value that re-opens epsilon/alpha to
    ``cfg.reopen_frac`` of their initial values — never advancing the
    counter (a step already below the reopen point stays put).

    Shared by :func:`reward_watchdog` (reward-collapse rewind between
    training episodes) and the serving path's overload watchdog
    (``soc.vecenv.ServeEnv``: sustained queue-full pressure re-opens
    exploration in-stream, same arithmetic)."""
    return jnp.minimum(
        step,
        (jnp.asarray(cfg.decay_steps, jnp.float32)
         * (1.0 - cfg.reopen_frac)).astype(jnp.int32))


def reward_watchdog(cfg: QConfig, qs: QState, ep_reward, best):
    """Reward-collapse watchdog: re-open exploration when an episode's
    reward collapses relative to the best episode seen so far.

    ``ep_reward`` is the (masked-mean) reward of the episode just
    finished, ``best`` the running best (carry ``-inf`` initially).  When
    ``ep_reward < cfg.collapse_frac * best`` on a still-training agent,
    the decay counter is wound back to ``decay_steps * (1 -
    reopen_frac)`` so epsilon/alpha re-open to ``reopen_frac`` of their
    initial values — the fault-degraded SoC no longer matches the learned
    table, and a near-zero epsilon would lock the stale policy in.  The
    running best also resets to the collapsed value so a *persistently*
    degraded plateau doesn't re-trigger every episode.

    With ``cfg.collapse_frac == 0`` (the default) every lane of this is
    ``where(False, _, x)`` — the returned state is bitwise ``qs``, which
    keeps healthy training runs identical to the watchdog-free program.

    Returns ``(new_qs, new_best)``.
    """
    ep_reward = jnp.asarray(ep_reward, jnp.float32)
    enabled = jnp.asarray(cfg.collapse_frac, jnp.float32) > 0.0
    collapsed = (enabled & ~qs.frozen & (best > 0.0)
                 & (ep_reward < cfg.collapse_frac * best))
    reopened = reopen_step(cfg, qs.step)
    new_qs = qs._replace(step=jnp.where(collapsed, reopened, qs.step))
    new_best = jnp.where(collapsed, ep_reward, jnp.maximum(best, ep_reward))
    return new_qs, new_best


# ---------------------------------------------------------------------------
# debug_finite: host-side finiteness tripwires (off by default everywhere).
# ---------------------------------------------------------------------------
# Violations are also recorded here because an exception raised inside a
# jax.debug.callback only surfaces (as jaxlib's CpuCallback XlaRuntimeError)
# when the result is materialized — tests and post-mortems read the log for
# a deterministic account of WHAT went non-finite and WHERE.
_finite_violations: list[str] = []


def finite_violations() -> list[str]:
    """Snapshot of the recorded finiteness violations (newest last)."""
    return list(_finite_violations)


def clear_finite_violations() -> None:
    _finite_violations.clear()


def _host_assert_finite(tag: str, **arrays) -> None:
    bad = sorted(k for k, v in arrays.items()
                 if not np.all(np.isfinite(np.asarray(v, np.float64))))
    if bad:
        msg = f"{tag}: non-finite {', '.join(bad)}"
        _finite_violations.append(msg)
        raise FloatingPointError(msg)


def debug_finite_check(tag: str, **arrays) -> None:
    """Insert a host callback asserting every named array is finite.

    Works under jit/vmap/scan via ``jax.debug.callback``; a violation is
    appended to :func:`finite_violations` and raised as
    ``FloatingPointError`` (surfacing as an ``XlaRuntimeError`` at the
    blocking site when traced).  Do not call on hot paths — that is why
    every ``debug_finite=`` flag defaults to False."""
    jax.debug.callback(functools.partial(_host_assert_finite, tag), **arrays)
