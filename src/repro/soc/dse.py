"""Generative SoC design space: budgeted sampling + bucketed co-search.

The paper evaluates Cohmeleon on eight hand-written SoCs (Table 4); its
core claim — the best coherence mode depends on accelerator, workload
AND architecture — begs the design-space question this module answers:
*which architectures make learned coherence win biggest?*

Two halves:

  * :func:`sample_socs` draws design points (accelerator counts and
    pattern mixes, cache sizes, DDR channels, CPU counts, NoC dims,
    ``no_private_cache`` masks) under a lumos-style area/bandwidth
    :class:`~repro.soc.config.SoCBudget`.  Over-budget draws are
    repaired deterministically (shrink LLC, shrink L2, drop
    accelerators, ...) so every emitted :class:`SoCConfig` validates and
    fits the envelope, and each design point carries its own
    deterministic seed (apps, tile striping, episode keys derive from
    it, so every per-SoC input is independent of sample count and of
    how the sweep is bucketed; deterministic-family metrics are bitwise
    bucketing-invariant, while keyed families redraw their pre-sampled
    noise when a bucket's padded scan length changes — jax's threefry
    pairs counter halves by total draw length).
  * :func:`run_sweep` pushes hundreds of generated SoCs through k-way
    :func:`~repro.soc.stacked.compile_apps_bucketed`, trains one
    Cohmeleon agent per SoC with ONE
    :meth:`~repro.soc.stacked.StackedVecEnv.train_batched` call per
    bucket, evaluates the whole policy suite (fixed modes, random,
    manual Algorithm 1, the trained agents) with ONE
    :meth:`~repro.soc.stacked.StackedVecEnv.episodes` call per bucket,
    reassembles per-lane metrics back to sample order
    (:func:`~repro.soc.stacked.reassemble_lanes`), and regresses the
    learned-policy win margins (speedup and off-chip reduction vs the
    NON_COH baseline) against the sampler axes.

``benchmarks/fig12_dse.py`` is the figure driver; the committed report
ranks architectures and sampler axes by learned-coherence margin.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qlearn
from repro.core.modes import CoherenceMode
from repro.core.policies import FixedHomogeneous, ManualPolicy, RandomPolicy
from repro.core.rewards import PAPER_DEFAULT_WEIGHTS, stack_weights
from repro.soc import vecenv as vec
from repro.soc.accelerators import (PATTERN_NAMES, PROFILES)
from repro.soc.config import (DEFAULT_BUDGET, KB, MemTimings, SoCBudget,
                              SoCConfig, budget_report, soc_offchip_bw)
from repro.soc.stacked import (StackedVecEnv, _compile_lanes,
                               _stack_compiled, length_buckets,
                               reassemble_lanes)

# Accelerators grouped by access pattern (streaming / strided /
# irregular) — the sampler draws a pattern mix first so the mix axes
# vary widely instead of concentrating at the suite's 8/3/1 split.
_BY_PATTERN = tuple(
    tuple(n for n, p in PROFILES.items() if p.pattern == pat)
    for pat in range(len(PATTERN_NAMES)))

L2_CHOICES = (16 * KB, 32 * KB, 64 * KB, 128 * KB)
LLC_CHOICES = (128 * KB, 256 * KB, 512 * KB, 1024 * KB)

# Sampler axes regressed against the learned-policy margin.  NoC dims
# are excluded: the grid is the smallest that fits the occupants, so
# its size is collinear with the count axes (and only costs area).
FEATURE_AXES = (
    "n_accs", "n_cpus", "n_mem_tiles", "l2_kb", "llc_slice_kb",
    "no_l2_frac", "frac_streaming", "frac_strided", "frac_irregular",
    "mean_compute_per_byte", "mean_reuse", "mean_burst",
    "area_frac", "bw_per_acc",
)

EVAL_FAMILIES = tuple(FixedHomogeneous(m).name for m in CoherenceMode) + (
    "random", "manual", "cohmeleon")
_BASE_IDX = 0            # NON_COH_DMA row == the normalization baseline
_N_FIXED = len(CoherenceMode)


@dataclasses.dataclass(frozen=True)
class SampledSoC:
    """One generated design point: validated config + deterministic seed
    + the raw sampler-axis values (the regression features)."""

    config: SoCConfig
    seed: int            # per-config seed (apps, tile striping, keys)
    axes: dict


def config_seed(key: int, i: int) -> int:
    """Deterministic per-config seed — depends only on (key, i), never on
    the sample count or bucket layout."""
    return int(np.random.SeedSequence([key, i]).generate_state(1)[0]
               % np.uint32(2 ** 31 - 1))


def _noc_dims(occupants: int) -> tuple[int, int]:
    """Smallest near-square grid with at least ``occupants`` tiles."""
    rows = int(math.ceil(math.sqrt(occupants)))
    cols = int(math.ceil(occupants / rows))
    return rows, cols


def _build(name: str, d: dict) -> SoCConfig:
    rows, cols = _noc_dims(d["n_accs"] + d["n_cpus"] + d["n_mem_tiles"])
    return SoCConfig(
        name=name, n_accs=d["n_accs"], noc_rows=rows, noc_cols=cols,
        n_cpus=d["n_cpus"], n_mem_tiles=d["n_mem_tiles"],
        llc_slice_bytes=d["llc_slice"], l2_bytes=d["l2"],
        accelerators=tuple(d["accs"][:d["n_accs"]]),
        no_private_cache=tuple(i for i in d["no_l2"] if i < d["n_accs"]))


def _sample_one(rng: np.random.Generator, name: str, budget: SoCBudget,
                min_accs: int, max_accs: int) -> tuple[SoCConfig, dict]:
    """Draw one design point, then repair it deterministically until it
    fits the budget (shrink LLC -> shrink L2 -> drop accelerators ->
    drop DDR channels -> drop CPUs, cheapest-first)."""
    n_accs = int(rng.integers(min_accs, max_accs + 1))
    mix = rng.dirichlet(np.ones(len(PATTERN_NAMES)))
    patterns = rng.choice(len(PATTERN_NAMES), size=n_accs, p=mix)
    accs = [str(rng.choice(_BY_PATTERN[p])) for p in patterns]
    no_l2_frac = float(rng.uniform(0.0, 0.4))
    d = {
        "n_accs": n_accs,
        "accs": accs,
        "n_cpus": int(rng.choice([1, 2, 4])),
        "n_mem_tiles": int(rng.choice([1, 2, 4])),
        "l2": int(rng.choice(L2_CHOICES)),
        "llc_slice": int(rng.choice(LLC_CHOICES)),
        "no_l2": sorted(int(i) for i in np.nonzero(
            rng.random(n_accs) < no_l2_frac)[0]),
    }
    # Bandwidth budget first: each DDR channel costs dram_bw bytes/cycle.
    dram_bw = MemTimings().dram_bw
    while (d["n_mem_tiles"] > 1
           and d["n_mem_tiles"] * dram_bw > budget.max_offchip_bw):
        d["n_mem_tiles"] //= 2
    # Area budget: shrink until the report says it fits.
    while True:
        cfg = _build(name, d)
        rep = budget_report(cfg, budget)
        if rep["within_budget"]:
            break
        if d["llc_slice"] > LLC_CHOICES[0]:
            d["llc_slice"] //= 2
        elif d["l2"] > L2_CHOICES[0]:
            d["l2"] //= 2
        elif d["n_accs"] > max(2, min(min_accs, 2)):
            d["n_accs"] -= 1
        elif d["n_mem_tiles"] > 1:
            d["n_mem_tiles"] -= 1
        elif d["n_cpus"] > 1:
            d["n_cpus"] -= 1
        else:
            raise ValueError(f"budget {budget} too tight for any design")

    profs = [PROFILES[n] for n in cfg.accelerators]
    pat = np.asarray([p.pattern for p in profs])
    axes = {
        "n_accs": cfg.n_accs,
        "n_cpus": cfg.n_cpus,
        "n_mem_tiles": cfg.n_mem_tiles,
        "noc_tiles": cfg.noc_rows * cfg.noc_cols,
        "l2_kb": cfg.l2_bytes // KB,
        "llc_slice_kb": cfg.llc_slice_bytes // KB,
        "no_l2_frac": len(cfg.no_private_cache) / cfg.n_accs,
        "frac_streaming": float(np.mean(pat == 0)),
        "frac_strided": float(np.mean(pat == 1)),
        "frac_irregular": float(np.mean(pat == 2)),
        "mean_compute_per_byte": float(np.mean(
            [p.compute_per_byte for p in profs])),
        "mean_reuse": float(np.mean([p.reuse for p in profs])),
        "mean_burst": float(np.mean([p.burst_bytes for p in profs])),
        "area": rep["area"],
        "area_frac": rep["area_frac"],
        "offchip_bw": rep["offchip_bw"],
        "bw_per_acc": soc_offchip_bw(cfg) / cfg.n_accs,
    }
    return cfg, axes


def sample_socs(key: int, n: int, budget: SoCBudget | None = None, *,
                min_accs: int = 4, max_accs: int = 16
                ) -> list[SampledSoC]:
    """Draw ``n`` validated, budget-fitting design points.

    Each point is sampled from its own ``SeedSequence([key, i])`` stream
    and carries :func:`config_seed`'s deterministic per-config seed —
    sample ``i`` is identical no matter how many points are drawn."""
    budget = budget or DEFAULT_BUDGET
    out = []
    for i in range(n):
        rng = np.random.default_rng(np.random.SeedSequence([key, i]))
        cfg, axes = _sample_one(rng, f"dse{key}-{i}", budget,
                                min_accs, max_accs)
        out.append(SampledSoC(config=cfg, seed=config_seed(key, i),
                              axes=axes))
    return out


# ------------------------------------------------------------------ sweep
def _eval_keys(seeds: np.ndarray, n_policies: int) -> jnp.ndarray:
    """(K, N, 2) evaluation keys derived from per-config seeds — bucket-
    and sample-count-invariant, so deterministic-family metrics from
    bucketed runs reassemble bitwise against a single stacked call."""
    flat = (seeds[:, None].astype(np.int64) * 131 + np.arange(n_policies)
            ) % (2 ** 31 - 1)
    return jax.vmap(jax.random.PRNGKey)(
        jnp.asarray(flat.ravel(), jnp.uint32)).reshape(
            len(seeds), n_policies, 2)


def _bucket_norms(sub: StackedVecEnv, st_iters, st_eval,
                  seeds_g: np.ndarray, iters: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Train one agent per lane, then evaluate the whole suite in one
    episodes call; returns (norm_time, norm_mem), each (K_g, N)."""
    cfg = qlearn.QConfig(decay_steps=jnp.asarray(
        [s * iters for s in st_iters[0].n_steps], jnp.int32))
    tkeys = jax.vmap(jax.random.PRNGKey)(
        jnp.asarray(seeds_g, jnp.uint32)).reshape(len(seeds_g), 1, 2)
    qs, _ = sub.train_batched(
        st_iters, cfg, stack_weights([PAPER_DEFAULT_WEIGHTS]), tkeys)

    suite = [FixedHomogeneous(m) for m in CoherenceMode]
    suite += [RandomPolicy(), ManualPolicy()]
    det = sub.lower(st_eval, suite)
    learned = sub.lower_qstates(st_eval, qs)
    specs = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=1), det, learned)
    keys = _eval_keys(seeds_g, len(EVAL_FAMILIES))
    res = sub.episodes(st_eval, specs, cfg, keys=keys)
    base = jax.tree_util.tree_map(lambda x: x[:, _BASE_IDX], res)
    nt, nm = jax.vmap(jax.vmap(vec.normalized_metrics,
                               in_axes=(0, None, None)),
                      in_axes=(0, 0, 0))(res, base, st_eval.phase_mask)
    return np.asarray(nt), np.asarray(nm)


def rank_axes(samples: Sequence[SampledSoC],
              targets: dict[str, np.ndarray]) -> dict:
    """Standardized least-squares regression of each target (e.g. the
    learned speedup margin) on :data:`FEATURE_AXES`; axes ranked by
    coefficient magnitude.  Constant axes get coefficient 0."""
    X = np.asarray([[s.axes[a] for a in FEATURE_AXES] for s in samples],
                   np.float64)
    mu, sd = X.mean(axis=0), X.std(axis=0)
    keep = sd > 1e-12
    Z = np.zeros_like(X)
    Z[:, keep] = (X[:, keep] - mu[keep]) / sd[keep]
    A = np.concatenate([np.ones((len(X), 1)), Z], axis=1)
    out = {}
    for name, y in targets.items():
        y = np.asarray(y, np.float64)
        coef, *_ = np.linalg.lstsq(A, y, rcond=None)
        pred = A @ coef
        ss_res = float(np.sum((y - pred) ** 2))
        ss_tot = float(np.sum((y - y.mean()) ** 2))
        ranked = sorted(zip(FEATURE_AXES, coef[1:].tolist()),
                        key=lambda kv: -abs(kv[1]))
        out[name] = {
            "ranked_coefficients": [[a, c] for a, c in ranked],
            "r2": 1.0 - ss_res / max(ss_tot, 1e-30),
        }
    return out


def run_sweep(samples: Sequence[SampledSoC], *, iters: int = 3,
              n_phases: int = 3, max_buckets: int = 4,
              min_gain: float = 0.02) -> dict:
    """Train + evaluate every sampled SoC in at most ``max_buckets``
    batched (train, eval) call pairs and reduce to per-architecture win
    margins.

    Per bucket: ONE :meth:`StackedVecEnv.train_batched` call (one agent
    per lane, per-lane decay horizons) and ONE
    :meth:`StackedVecEnv.episodes` call evaluating the full suite —
    fixed modes, random, manual, and the freshly trained agents — with
    the NON_COH row of the same call as the normalization baseline.
    Per-config seeds drive app generation, tile striping and episode
    keys, so every per-SoC input — and every deterministic-family
    metric — is independent of bucketing; keyed families (random,
    cohmeleon) consume noise pre-sampled at the bucket's padded scan
    length, so their draws differ across bucket layouts."""
    from repro.soc.apps import make_application

    socs = [s.config for s in samples]
    seeds = np.asarray([s.seed for s in samples], np.int64)
    env = StackedVecEnv(socs)

    train_apps = [make_application(c, seed=s.seed, n_phases=n_phases)
                  for c, s in zip(socs, samples)]
    eval_apps = [make_application(c, seed=s.seed + 1, n_phases=n_phases)
                 for c, s in zip(socs, samples)]
    compiled_iters = [
        _compile_lanes(train_apps, socs, [int(s) + it for s in seeds])
        for it in range(iters)]
    compiled_eval = _compile_lanes(eval_apps, socs,
                                   [int(s) + 7919 for s in seeds])
    lengths = [c.n_steps for c in compiled_iters[0]]
    groups = length_buckets(lengths, max_buckets=max_buckets,
                            min_gain=min_gain)

    def volume(lens, gs):
        return sum(len(g) * max(lens[i] for i in g) for g in gs)

    eval_lengths = [c.n_steps for c in compiled_eval]
    vol_single = (iters * volume(lengths, [list(range(len(socs)))])
                  + volume(eval_lengths, [list(range(len(socs)))]))
    vol_bucketed = (iters * volume(lengths, groups)
                    + volume(eval_lengths, groups))
    real = iters * sum(lengths) + sum(eval_lengths)

    parts, subs = [], []
    for g in groups:
        sub = env.sublanes(g)
        subs.append(sub)
        socs_g = [socs[i] for i in g]
        st_iters = [_stack_compiled([compiled_iters[it][i] for i in g],
                                    socs_g) for it in range(iters)]
        st_eval = _stack_compiled([compiled_eval[i] for i in g], socs_g)
        parts.append(_bucket_norms(sub, st_iters, st_eval,
                                   seeds[list(g)], iters))
    nt = reassemble_lanes(groups, [p[0] for p in parts])
    nm = reassemble_lanes(groups, [p[1] for p in parts])

    fixed_t, fixed_m = nt[:, :_N_FIXED], nm[:, :_N_FIXED]
    coh_t, coh_m = nt[:, -1], nm[:, -1]
    margins = {
        "speedup_vs_noncoh": 1.0 - coh_t,
        "offchip_reduction_vs_noncoh": 1.0 - coh_m,
        "speedup_vs_fixed_mean":
            (fixed_t.mean(axis=1) - coh_t) / fixed_t.mean(axis=1),
        "offchip_reduction_vs_fixed_mean":
            (fixed_m.mean(axis=1) - coh_m) / fixed_m.mean(axis=1),
        "speedup_vs_best_fixed":
            (fixed_t.min(axis=1) - coh_t) / fixed_t.min(axis=1),
    }
    train_calls = sum(s.calls["train"] for s in subs)
    eval_calls = sum(s.calls["episodes"] for s in subs)
    return {
        "n_socs": len(samples),
        "families": list(EVAL_FAMILIES),
        "norm_time": nt,
        "norm_mem": nm,
        "margins": margins,
        "groups": [list(g) for g in groups],
        "calls": {"train": int(train_calls), "eval": int(eval_calls),
                  "n_buckets": len(groups), "max_buckets": max_buckets},
        "waste": {
            "padded_volume_single_call": int(vol_single),
            "padded_volume_bucketed": int(vol_bucketed),
            "real_invocations": int(real),
            "padded_waste_single_call": 1.0 - real / vol_single,
            "padded_waste_bucketed": 1.0 - real / vol_bucketed,
            "waste_reduction": (vol_single - vol_bucketed) / vol_single,
        },
        "axis_ranking": rank_axes(samples, {
            "speedup_vs_noncoh": margins["speedup_vs_noncoh"],
            "offchip_reduction_vs_noncoh":
                margins["offchip_reduction_vs_noncoh"],
        }),
    }
