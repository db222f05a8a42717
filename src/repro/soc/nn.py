"""Function-approximation policy subsystem — a tiny MLP Q-network.

The tabular agent (:mod:`repro.core.qlearn`) can only serve the 243
Table-3 buckets it has visited: an unseen application or a freshly
sampled SoC (``soc.dse``) lands in optimistic all-tie rows and degrades
toward the Random policy.  This module replaces the table with a small
packed MLP over normalized *sense features* — footprint, tile count,
active-accelerator/DDR/LLC pressure, plus HyDRA-style deadline-slack and
reuse-distance signals from the serving path — trained with the paper's
contextual-bandit semi-gradient TD update ``delta = Q(s, a) - R``.

Design constraints (all load-bearing):

  * **One packed weight array.**  :class:`MLPQState` carries every layer
    in a single ``(rows, cols)`` float32 ``wpack`` (per layer: ``nin``
    weight rows then one bias row, columns padded to the widest layer).
    A single rectangular leaf rides the fused-step scan carry, the
    Pallas kernel's VMEM scratch and checkpoints without pytree surgery.
  * **Pallas-safe arithmetic.**  :func:`forward_packed`,
    :func:`td_update_packed` and :func:`step_features` are called from
    inside the fused kernel body (:mod:`repro.kernels.soc_step.ref`), so
    they use static slices, 2-D ``broadcasted_iota`` and elementwise
    broadcast-sums (no ``jnp.dot`` — the layers are far below MXU tile
    sizes) and never capture device arrays.
  * **Static architecture.**  :class:`MLPConfig` is registered as a
    static pytree node, so it rides *inside* :class:`MLPQState` (and
    therefore inside ``PolicySpec``) as part of the treedef — jit keys
    on it, ``vmap``/``tree_map`` skip it, and stacking specs with
    mismatched configs fails loudly at the treedef level.
  * **Bitwise dead branch.**  A :func:`frozen_mlp_qstate` placeholder
    attached to a table spec (``qfun=False``) must leave both the
    Q-table and the placeholder weights bitwise untouched; every update
    here is a ``jnp.where`` whose gate is exactly False on that branch.
  * **Degradation for free.**  The MLP's Q-row feeds the same
    ``qlearn.row_select_presampled`` as the table row, so non-finite
    weights (fault storms, PR 7) hit its existing non-finite-row
    fallback and the step serves NON_COH without new machinery.

The portfolio trainer (:func:`train_portfolio`) trains ONE shared
network across (apps x SoCs) pairs with per-iteration federated
averaging of the packed weights; ``benchmarks/fig13_generalize.py``
evaluates it against the shared tabular agent on held-out apps and
held-out DSE-sampled SoCs.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qlearn
from repro.core.modes import N_MODES
from repro.core.policies import Policy
from repro.core.state import N_STATES
from repro.core.vops import iota, vsum
from repro.soc.accelerators import IRREGULAR, PF, PROFILE_WIDTH

# Number of normalized sense features (the "sense" embedding).  Order is
# part of the spec — the DES mirror, the unfused step and the fused
# kernel all call :func:`step_features` so they cannot drift.
N_SENSE_FEATURES = 14


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    """Static network architecture (part of the pytree *structure*).

    ``features`` picks the input embedding: ``"sense"`` is the
    14-feature normalized snapshot; ``"onehot"`` embeds the Table-3
    state index as a one-hot vector (243 wide) — with ``hidden=()`` that
    is an exact linear re-parameterization of a Q-table, which is what
    the spec-lowering equivalence tests distill into.  ``lr`` is only
    the default :func:`init_mlp_qstate` bakes into the state's traced
    ``lr`` leaf."""

    features: str = "sense"
    hidden: tuple = (16, 16)
    lr: float = 0.05

    def __post_init__(self):
        object.__setattr__(self, "hidden", tuple(int(h) for h in self.hidden))
        if self.features not in ("sense", "onehot"):
            raise ValueError(f"unknown feature embedding {self.features!r}")


# Static registration: MLPConfig becomes treedef, not leaves — jit keys
# on it and vmap/tree_map pass it through untouched.
jax.tree_util.register_static(MLPConfig)


class MLPQState(NamedTuple):
    """The function-approximation agent — drop-in for ``qlearn.QState``.

    ``wpack`` is the packed weight stack (:func:`pack_shape`); ``lr``
    the traced learning-rate scale (the effective step size is
    ``alpha_t * lr`` with ``alpha_t`` the paper's decayed alpha, so the
    MLP follows the exact tabular decay protocol); ``step``/``frozen``
    mirror the tabular counters and drive the shared
    ``qlearn.decay_arrays`` schedule."""

    wpack: jnp.ndarray   # (R, C) float32 packed weights
    lr: jnp.ndarray      # () float32 learning-rate scale
    step: jnp.ndarray    # () int32 training invocations so far
    frozen: jnp.ndarray  # () bool
    cfg: MLPConfig       # static (treedef) architecture


def mlp_dims(cfg: MLPConfig) -> tuple:
    """Layer widths ``(n_in, *hidden, n_actions)`` for ``cfg``."""
    n_in = N_SENSE_FEATURES if cfg.features == "sense" else N_STATES
    return (n_in, *cfg.hidden, N_MODES)


def pack_shape(dims: Sequence[int]) -> tuple:
    """(rows, cols) of the packed weight array for ``dims``.

    Layer ``l`` occupies ``dims[l]`` weight rows followed by one bias
    row; columns pad to the widest output so one rectangle holds all."""
    return sum(d + 1 for d in dims[:-1]), max(dims[1:])


def forward_packed(wpack, x, dims) -> jnp.ndarray:
    """Q-row for feature vector ``x``: ReLU MLP over the packed weights.

    The matmul is an elementwise broadcast-sum (``sum(W * x[:, None])``)
    — exact for one-hot inputs (the off rows contribute signed zeros),
    VPU-friendly at these tiny widths, and Pallas-safe."""
    h = x
    off = 0
    last = len(dims) - 2
    for l in range(len(dims) - 1):
        nin, nout = dims[l], dims[l + 1]
        w = wpack[off:off + nin, :nout]
        z = jnp.sum(w * h[:, None], axis=0) + wpack[off + nin, :nout]
        h = z if l == last else jnp.maximum(z, 0.0)
        off += nin + 1
    return h


def td_update_packed(wpack, x, action, reward, lr_eff, dims, gate):
    """One semi-gradient TD step on the packed weights.

    Contextual-bandit target (the paper's update has no bootstrap):
    ``delta = Q(s, a) - R``, hand-backpropagated over the packed layout
    (static Python loop — the architecture is static).  The update is a
    single ``jnp.where``: it fires only when ``gate`` holds (the spec's
    ``qfun`` flag, AND the row-validity gate on padded/shed steps), the
    effective step size is positive (frozen or fully-decayed agents are
    exact no-ops) and ``delta`` is finite — non-finite weights, features
    or rewards can never poison the pack (``0 * NaN`` is NaN, so gating
    multiplicatively would not be safe; selecting is)."""
    # Forward, keeping per-layer activations for the backward pass.
    hs = [x]
    off = 0
    offs = []
    last = len(dims) - 2
    for l in range(len(dims) - 1):
        nin, nout = dims[l], dims[l + 1]
        offs.append(off)
        w = wpack[off:off + nin, :nout]
        z = jnp.sum(w * hs[-1][:, None], axis=0) + wpack[off + nin, :nout]
        hs.append(z if l == last else jnp.maximum(z, 0.0))
        off += nin + 1

    f32 = jnp.float32
    n_act = dims[-1]
    hot = (iota(n_act) == action).astype(f32)
    q_a = jnp.sum(hs[-1] * hot)
    delta = q_a - reward

    cols = wpack.shape[-1]
    g = hot * delta                      # dL/dz of the output layer
    rows = [None] * (len(dims) - 1)
    for l in range(len(dims) - 2, -1, -1):
        nin, nout = dims[l], dims[l + 1]
        dw = hs[l][:, None] * g[None, :]                   # (nin, nout)
        db = g[None, :]                                    # (1, nout)
        blk = jnp.concatenate([dw, db], axis=0)
        if cols > nout:
            blk = jnp.concatenate(
                [blk, jnp.zeros((nin + 1, cols - nout), f32)], axis=1)
        rows[l] = blk
        if l > 0:
            w = wpack[offs[l]:offs[l] + nin, :nout]
            g = jnp.sum(w * g[None, :], axis=1) * (hs[l] > 0.0).astype(f32)
    grad = jnp.concatenate(rows, axis=0)

    ok = gate & jnp.isfinite(delta) & (lr_eff > 0.0)
    return jnp.where(ok, wpack - lr_eff * grad, wpack)


def step_features(feats: str, s, state_idx, *, footprint, tiles, omask,
                  omodes, ofps, odram, warm_t, profile, slack, reuse):
    """The per-invocation input embedding, shared by every engine.

    ``feats="onehot"`` embeds the sensed Table-3 index (the exact-table
    re-parameterization); ``"sense"`` builds the 14 normalized features
    below from quantities the fused step already has in hand.  The
    unfused step, the serving step and the DES mirror call this with
    bitwise-identical inputs, so the embeddings (and hence selections)
    cannot drift between engines.

    Sense features (all roughly [0, 1]; squashes are odd and bounded):
    log/capacity-relative footprint (vs L2 and total LLC), needed-tile
    fraction, counts of active / LLC-routed / non-coherent concurrent
    accelerators, aggregate LLC footprint pressure, aggregate DDR
    bandwidth demand pressure, inter-stage warmth, the irregular-access
    profile flag, log compute-per-byte, and the HyDRA-style
    deadline-slack and reuse-distance squashes (zero on the episodic
    path; the serving step feeds real values)."""
    f32 = jnp.float32
    if feats == "onehot":
        return (iota(N_STATES) == state_idx).astype(f32)
    llc_total = s.llc_slice_bytes * s.n_mem_tiles
    n_tiles = tiles.shape[-1]
    fp = footprint.astype(f32) if hasattr(footprint, "astype") else f32(footprint)
    omask_f = omask.astype(f32)
    cached = omask & (omodes > 0)          # routes through the LLC
    non_coh = omask & (omodes == 0)
    sl = slack * np.float32(1e-6)
    ru = reuse * np.float32(1e-6)
    return jnp.stack([
        jnp.log2(1.0 + fp) * np.float32(1.0 / 32.0),
        jnp.clip(fp / s.l2_bytes, 0.0, 4.0) * np.float32(0.25),
        jnp.clip(fp / llc_total, 0.0, 4.0) * np.float32(0.25),
        jnp.sum(tiles.astype(f32)) / np.float32(n_tiles),
        vsum(omask_f) * np.float32(0.125),
        vsum(cached.astype(f32)) * np.float32(0.125),
        vsum(non_coh.astype(f32)) * np.float32(0.125),
        jnp.clip(vsum(ofps) / llc_total, 0.0, 4.0) * np.float32(0.25),
        jnp.clip(vsum(odram) / s.dram_bw, 0.0, 4.0) * np.float32(0.25),
        warm_t,
        (profile[PF.PATTERN] == np.float32(IRREGULAR)).astype(f32),
        jnp.log2(1.0 + profile[PF.COMPUTE]) * np.float32(0.125),
        sl / (1.0 + jnp.abs(sl)),
        ru / (1.0 + jnp.abs(ru)),
    ])


# --------------------------------------------------------------------------
# State constructors
# --------------------------------------------------------------------------

def init_mlp_qstate(key, cfg: MLPConfig = MLPConfig(),
                    q_init: float = 1.0) -> MLPQState:
    """A fresh trainable network.

    Hidden layers draw He-scaled Gaussians; the output layer starts at
    exactly ``W=0, b=q_init`` so every state's Q-row is an all-tie at
    the tabular optimistic init — the untrained MLP equals the Random
    policy under randomized argmax, preserving the paper's "iteration 0
    == Random" property just like ``qlearn.init_qstate``."""
    dims = mlp_dims(cfg)
    rows, cols = pack_shape(dims)
    wpack = jnp.zeros((rows, cols), jnp.float32)
    off = 0
    last = len(dims) - 2
    for l in range(len(dims) - 1):
        nin, nout = dims[l], dims[l + 1]
        if l == last:
            wpack = wpack.at[off + nin, :nout].set(jnp.float32(q_init))
        else:
            key, sub = jax.random.split(key)
            w = jax.random.normal(sub, (nin, nout), jnp.float32)
            wpack = wpack.at[off:off + nin, :nout].set(
                w * np.float32(np.sqrt(2.0 / nin)))
        off += nin + 1
    return MLPQState(wpack=wpack, lr=jnp.asarray(cfg.lr, jnp.float32),
                     step=jnp.zeros((), jnp.int32),
                     frozen=jnp.zeros((), bool), cfg=cfg)


def frozen_mlp_qstate(cfg: MLPConfig = MLPConfig(),
                      q_init: float = 1.0) -> MLPQState:
    """The inert placeholder a non-``qfun`` PolicySpec carries — the MLP
    analogue of ``qlearn.frozen_qstate``.  Deterministic (no PRNG) and
    frozen: the fused step's update gate is exactly False on it, so
    attaching it to a table spec is a bitwise no-op (pinned by the
    dead-branch tests)."""
    dims = mlp_dims(cfg)
    rows, cols = pack_shape(dims)
    nin, nout = dims[-2], dims[-1]
    wpack = jnp.zeros((rows, cols), jnp.float32).at[
        rows - 1, :nout].set(jnp.float32(q_init))
    return MLPQState(wpack=wpack, lr=jnp.zeros((), jnp.float32),
                     step=jnp.zeros((), jnp.int32),
                     frozen=jnp.ones((), bool), cfg=cfg)


def freeze(mlp: MLPQState) -> MLPQState:
    """Disable further updates (evaluate the converged network)."""
    return mlp._replace(frozen=jnp.ones((), bool))


def mlp_from_qtable(qtable, lr: float = 0.0) -> MLPQState:
    """Distill a Q-table into an exactly-equivalent linear MLP.

    One-hot state embedding, no hidden layers, weights = the table,
    biases = 0: the forward broadcast-sum reduces to the gathered table
    row plus signed zeros, so epsilon-greedy selection over the MLP's
    Q-row picks *identical* modes to the table spec (the spec-lowering
    equivalence contract in ``tests/test_policy_spec.py``)."""
    qtable = jnp.asarray(qtable, jnp.float32)
    n_states, n_actions = qtable.shape
    cfg = MLPConfig(features="onehot", hidden=(), lr=float(lr))
    rows, cols = pack_shape(mlp_dims(cfg))
    assert (rows, cols) == (n_states + 1, n_actions)
    wpack = jnp.zeros((rows, cols), jnp.float32).at[:n_states, :].set(qtable)
    return MLPQState(wpack=wpack, lr=jnp.asarray(lr, jnp.float32),
                     step=jnp.zeros((), jnp.int32),
                     frozen=jnp.zeros((), bool), cfg=cfg)


# --------------------------------------------------------------------------
# DES host mirror
# --------------------------------------------------------------------------

@jax.jit
def _forward_jit(wpack, feats_vec, cfg: MLPConfig):
    return forward_packed(wpack, feats_vec, mlp_dims(cfg))


class MLPQPolicy(Policy):
    """DES host mirror of the function-approximation agent.

    ``decide`` rebuilds the same feature vector the vectorized engines
    feed :func:`step_features` (the fidelity cross-check pins phase-time
    agreement on single-thread apps, where the concurrent-set features
    are trivially equal) and greedily argmaxes the network's Q-row over
    the available modes.  ``lower`` emits the ``qfun`` PolicySpec, so
    the one-line table->MLP swap in the examples is literally swapping
    this class for ``QPolicy``."""

    name = "cohmeleon-mlp"

    def __init__(self, mlp: MLPQState | None = None,
                 cfg: MLPConfig = MLPConfig(), seed: int = 0):
        self.mlp = (mlp if mlp is not None
                    else init_mlp_qstate(jax.random.PRNGKey(seed), cfg))

    def decide(self, ctx) -> int:
        from repro.soc.memsys import SoCStatic
        s = SoCStatic.from_config(ctx.soc)
        n_accs = ctx.soc.n_accs
        omodes = np.full((n_accs,), -1, np.int32)
        ofps = np.zeros((n_accs,), np.float32)
        afps = (ctx.active_footprints if ctx.active_footprints is not None
                else [0.0] * len(ctx.active_modes))
        for i, (m, fp) in enumerate(zip(ctx.active_modes, afps)):
            if i >= n_accs:
                break
            omodes[i] = m
            ofps[i] = fp
        omask = omodes >= 0
        tiles = (np.asarray(ctx.target_tiles, bool)
                 if ctx.target_tiles is not None
                 else np.zeros((ctx.soc.n_mem_tiles,), bool))
        profile = (np.asarray(ctx.profile, np.float32)
                   if ctx.profile is not None
                   else np.zeros((PROFILE_WIDTH,), np.float32))
        feats = step_features(
            self.mlp.cfg.features, s, jnp.asarray(ctx.state_idx, jnp.int32),
            footprint=jnp.asarray(ctx.footprint, jnp.float32),
            tiles=jnp.asarray(tiles), omask=jnp.asarray(omask),
            omodes=jnp.asarray(omodes), ofps=jnp.asarray(ofps),
            odram=jnp.zeros((n_accs,), jnp.float32),
            warm_t=jnp.asarray(ctx.warm, jnp.float32),
            profile=jnp.asarray(profile),
            slack=jnp.asarray(ctx.slack, jnp.float32),
            reuse=jnp.asarray(ctx.reuse, jnp.float32))
        row = np.asarray(_forward_jit(self.mlp.wpack, feats, self.mlp.cfg))
        masked = np.where(np.asarray(ctx.available, bool), row, -np.inf)
        if not np.all(np.isfinite(row)):
            return 0  # NON_COH fallback, mirroring row_select_presampled
        return int(np.argmax(masked))

    def lower(self, env, compiled):
        from repro.soc import vecenv as vec
        return vec.mlp_policy_spec(self.mlp, compiled.schedule)


# --------------------------------------------------------------------------
# Portfolio training: one shared network across (apps x SoCs)
# --------------------------------------------------------------------------

def _portfolio_call(env, compiled):
    """(cached) jitted B-seed training call for one (env, app) pair."""
    cache_key = ("mlp_portfolio", compiled.n_phases, compiled.n_threads)
    if cache_key not in env._train_cache:
        ep = env._episode_fn(compiled.n_phases, compiled.n_threads)

        def one(sched, spec, cfg, w, key):
            (_, mlp_f), res = ep(sched, spec, cfg, w, key, None)
            valid = sched.valid.astype(jnp.float32)
            mean_r = (jnp.sum(jnp.where(sched.valid, res.reward, 0.0))
                      / jnp.maximum(jnp.sum(valid), 1.0))
            return mlp_f, mean_r

        env._train_cache[cache_key] = jax.jit(
            jax.vmap(one, in_axes=(None, None, None, None, 0)))
    return env._train_cache[cache_key]


def train_portfolio(items, cfg, *, iterations: int = 6, batch: int = 2,
                    mcfg: MLPConfig = MLPConfig(), key=None,
                    weights=None, mlp: MLPQState | None = None,
                    manager=None):
    """Train ONE shared MLP across a portfolio of (env, apps) pairs.

    ``items`` is a sequence of ``(VecEnv, [CompiledApp, ...])`` pairs —
    one per (SoC, application); ``cfg`` is the tabular ``QConfig`` whose
    epsilon/alpha decay protocol the MLP follows exactly (``decay_steps``
    counts *total* invocations across the portfolio).  Each iteration
    runs one batched training episode per pair (``batch`` seeds vmapped
    in one jitted call) with the *current* shared weights, then
    federated-averages the resulting packs across every (pair x seed)
    lane — simple FedAvg, exact for the 1-lane case.  The shared step
    counter advances by the mean per-lane increment so the decay
    schedule tracks a single agent's.

    ``manager`` (a ``checkpoint.CheckpointManager``) makes the loop
    crash-resumable: the ``(MLPQState, iteration)`` snapshot is saved
    after every iteration and restored on entry, so an interrupted +
    resumed run ends bitwise-equal to an uninterrupted one (the
    per-iteration keys are derived by ``fold_in``, never carried).

    Returns ``(mlp, history)`` with ``history`` the (iterations,) mean
    training reward across the portfolio."""
    from repro.core import rewards
    if key is None:
        key = jax.random.PRNGKey(0)
    weights = weights if weights is not None else rewards.PAPER_DEFAULT_WEIGHTS
    if mlp is None:
        key, sub = jax.random.split(key)
        mlp = init_mlp_qstate(sub, mcfg)
    done = 0
    hist = np.zeros((iterations,), np.float32)
    if manager is not None and manager.latest_step() is not None:
        state = manager.restore({
            "mlp": mlp._replace(cfg=None), "hist": jnp.asarray(hist),
            "done": jnp.zeros((), jnp.int32)})
        mlp = state["mlp"]._replace(cfg=mlp.cfg)
        hist = np.array(state["hist"], np.float32)   # writable copy
        done = int(state["done"])

    from repro.soc import vecenv as vec
    for it in range(done, iterations):
        wpacks, steps, rs = [], [], []
        for j, (env, comps) in enumerate(items):
            comp = comps[it % len(comps)]
            spec = vec.mlp_policy_spec(mlp, comp.schedule)
            k = jax.random.fold_in(key, it * len(items) + j)
            ks = jax.random.split(k, batch)
            mlp_f, mean_r = _portfolio_call(env, comp)(
                comp.schedule, spec, cfg, weights, ks)
            wpacks.append(mlp_f.wpack)       # (batch, R, C)
            steps.append(mlp_f.step)         # (batch,)
            rs.append(mean_r)
        wall = jnp.concatenate(wpacks, axis=0)
        mlp = mlp._replace(
            wpack=jnp.mean(wall, axis=0),
            step=jnp.mean(jnp.concatenate(steps).astype(jnp.float32)
                          ).astype(jnp.int32))
        hist[it] = float(jnp.mean(jnp.concatenate(rs)))
        if manager is not None:
            manager.save(it + 1, {
                "mlp": mlp._replace(cfg=None), "hist": jnp.asarray(hist),
                "done": jnp.asarray(it + 1, jnp.int32)})
    if manager is not None:
        manager.wait()
    return mlp, jnp.asarray(hist)
