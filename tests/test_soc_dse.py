"""Generative design space (soc.dse) + the k-way bucketing it rides on.

Three contracts:

  * the budgeted sampler emits validated, budget-fitting, deterministic
    design points (and SoCConfig's own validator catches buggy ones);
  * k-way ``length_buckets`` partitions exactly, never wastes more
    padded volume than fewer buckets, and keeps the old 2-bucket
    behaviour;
  * per-lane metrics reassembled from bucketed sublane runs are
    BITWISE-equal to the single-call stacked run on the same lanes —
    padding rows/tiles/phases are inert down to the last ulp, which is
    what lets the sweep report per-SoC numbers independent of bucket
    layout.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.modes import CoherenceMode
from repro.core.policies import FixedHomogeneous, ManualPolicy
from repro.soc import dse, stacked as stk
from repro.soc.config import (DEFAULT_BUDGET, SOCS, SoCBudget, SoCConfig,
                              budget_report, soc_area, soc_offchip_bw)
from repro.soc.des import Application
from repro.soc.apps import make_phase


# ----------------------------------------------------------- config validator
def test_all_handwritten_socs_validate_and_fit_budget():
    for name, soc in SOCS.items():
        rep = budget_report(soc)   # __post_init__ already ran at import
        assert rep["within_budget"], (name, rep)
        assert soc_area(soc) > 0 and soc_offchip_bw(soc) > 0


@pytest.mark.parametrize("patch, match", [
    (dict(accelerators=("fft",)), "accelerator names"),
    (dict(no_private_cache=(7,)), "no_private_cache"),
    (dict(no_private_cache=(-1,)), "no_private_cache"),
    (dict(noc_rows=1, noc_cols=3), "tiles"),
    (dict(llc_slice_bytes=0), "llc_slice_bytes"),
    (dict(l2_bytes=-4), "l2_bytes"),
    (dict(n_accs=0, accelerators=()), "n_accs"),
])
def test_soc_config_rejects_broken_invariants(patch, match):
    base = dict(name="bad", n_accs=2, noc_rows=3, noc_cols=3, n_cpus=1,
                n_mem_tiles=1, llc_slice_bytes=1024, l2_bytes=512,
                accelerators=("fft", "gemm"))
    with pytest.raises(ValueError, match=match):
        SoCConfig(**{**base, **patch})


def test_soc_config_error_names_the_config_and_all_problems():
    with pytest.raises(ValueError) as ei:
        SoCConfig(name="frankensoc", n_accs=3, noc_rows=1, noc_cols=1,
                  n_cpus=1, n_mem_tiles=1, llc_slice_bytes=0, l2_bytes=8,
                  accelerators=("fft",))
    msg = str(ei.value)
    assert "frankensoc" in msg and "llc_slice_bytes" in msg
    assert "accelerator names" in msg and "tiles" in msg


# ------------------------------------------------------------------- sampler
def test_sampler_is_deterministic_and_count_independent():
    a = dse.sample_socs(3, 10)
    b = dse.sample_socs(3, 4)
    assert [s.config for s in b] == [s.config for s in a[:4]]
    assert [s.seed for s in b] == [s.seed for s in a[:4]]
    assert dse.sample_socs(4, 1)[0].config != a[0].config or (
        dse.sample_socs(4, 1)[0].seed != a[0].seed)


def test_sampled_socs_fit_budget_and_validate():
    budget = DEFAULT_BUDGET
    for s in dse.sample_socs(1, 24):
        rep = budget_report(s.config, budget)
        assert rep["within_budget"], (s.config.name, rep)
        assert len(s.config.accelerators) == s.config.n_accs
        assert all(0 <= i < s.config.n_accs
                   for i in s.config.no_private_cache)
        for axis in dse.FEATURE_AXES:
            assert np.isfinite(s.axes[axis]), axis


def test_sampler_repairs_into_a_tight_budget():
    tight = SoCBudget(max_area=14.0, max_offchip_bw=4.0)
    for s in dse.sample_socs(2, 8, budget=tight):
        rep = budget_report(s.config, tight)
        assert rep["within_budget"], (s.config.name, rep)
        assert s.config.n_mem_tiles == 1   # 4 bytes/cycle cap == 1 channel


def test_config_seeds_are_distinct():
    seeds = [s.seed for s in dse.sample_socs(0, 32)]
    assert len(set(seeds)) == len(seeds)


# --------------------------------------------------- k-way bucketing properties
def _padded_volume(lens, groups):
    return sum(len(g) * max(lens[i] for i in g) for g in groups)


def test_buckets_partition_and_volume_monotone_in_max_buckets():
    rng = np.random.default_rng(0)
    for _ in range(40):
        k = int(rng.integers(1, 24))
        lens = rng.integers(1, 400, size=k).tolist()
        prev_vol = None
        for mb in range(1, 7):
            groups = stk.length_buckets(lens, max_buckets=mb, min_gain=0.0)
            flat = sorted(i for g in groups for i in g)
            assert flat == list(range(k)), (lens, mb, groups)
            assert len(groups) <= max(1, mb)
            vol = _padded_volume(lens, groups)
            if prev_vol is not None:
                assert vol <= prev_vol, (lens, mb)
            prev_vol = vol
        # every bucket tight: its max is a real member length
        for g in groups:
            assert max(lens[i] for i in g) in [lens[i] for i in g]


def test_two_bucket_results_unchanged_and_min_gain_stop_rule():
    # the old single-cut behaviour, pinned
    assert stk.length_buckets([100, 101, 102]) == [[0, 1, 2]]
    assert stk.length_buckets([10, 11, 40]) == [[0, 1], [2]]
    # k-way splits where the old code raised
    assert stk.length_buckets([10, 10, 40, 40, 100], max_buckets=3,
                              min_gain=0.0) == [[0, 1], [2, 3], [4]]
    # min_gain gates EACH extra cut: the second cut's small gain is refused
    lens = [10, 10, 100, 100, 104]
    g2 = stk.length_buckets(lens, max_buckets=4, min_gain=0.05)
    assert g2 == [[0, 1], [2, 3, 4]]
    assert stk.length_buckets(lens, max_buckets=4, min_gain=0.0) \
        == [[0, 1], [2, 3], [4]]
    # uniform lengths never split, whatever the budget
    assert stk.length_buckets([7] * 5, max_buckets=5, min_gain=0.0) \
        == [[0, 1, 2, 3, 4]]


def test_compile_lanes_rejects_seed_length_mismatch():
    socs = [SOCS["SoC1"], SOCS["SoC2"]]
    apps = [_chain_app(soc, seed=i) for i, soc in enumerate(socs)]
    with pytest.raises(ValueError, match="2 per-lane seeds vs 3 apps"):
        stk.compile_apps_stacked(apps + [apps[0]], socs + [socs[0]],
                                 seed=[1, 2])
    with pytest.raises(ValueError, match="3 per-lane seeds vs 2 apps"):
        stk.compile_apps_stacked(apps, socs, seed=[1, 2, 3])
    # matching sequence still works and equals per-lane scalar compiles
    sa = stk.compile_apps_stacked(apps, socs, seed=[5, 6])
    assert sa.n_lanes == 2


def test_reassemble_lanes_rejects_non_partition():
    with pytest.raises(ValueError, match="partition"):
        stk.reassemble_lanes([[0, 1], [1, 2]],
                             [np.zeros(2), np.zeros(2)])


# ------------------------------------------- bitwise bucketed-vs-single contract
def _chain_app(soc, seed, n_phases=3):
    rng = np.random.default_rng(seed)
    phases = [
        make_phase(rng, soc, name=f"p{i}", n_threads=1 + (i % 2),
                   size_classes=[c], chain_len=3, loops=2 + i)
        for i, c in enumerate(("S", "M", "L", "XL")[:n_phases])
    ]
    return Application(name=f"{soc.name}-dse-chain", phases=phases)


@pytest.fixture(scope="module")
def fig9_like():
    """Four heterogeneous SoCs with deliberately divergent schedule
    lengths (the Fig. 9 regime that makes bucketing pay off)."""
    socs = [SOCS["SoC1"], SOCS["SoC2"], SOCS["SoC5"], SOCS["SoC6"]]
    apps = [_chain_app(soc, seed=20 + i, n_phases=2 + i % 3)
            for i, soc in enumerate(socs)]
    env = stk.StackedVecEnv(socs, seed=0)
    return socs, apps, env


def test_bucketed_metrics_bitwise_equal_single_call(fig9_like):
    """Per-lane normalized metrics from bucketed sublane runs, reassembled
    to lane order, are bitwise-equal to one stacked call over all lanes
    for every deterministic family (the fixed suite + manual Algorithm 1
    — the families fig9 pins).  Keyed families are excluded by
    construction: jax's threefry pairs counter halves by total draw
    length, so pre-sampled select noise legitimately differs when a
    bucket pads to a shorter scan."""
    import jax
    from repro.soc import vecenv as vec

    socs, apps, env = fig9_like
    seeds = [100 + i for i in range(len(socs))]
    suite = [FixedHomogeneous(m) for m in CoherenceMode] + [ManualPolicy()]
    lane_seeds = np.asarray(seeds, np.int64)

    def norms(sub_env, sa, lanes):
        specs = sub_env.lower(sa, suite)
        keys = dse._eval_keys(lane_seeds[lanes], len(suite))
        res = sub_env.episodes(sa, specs, keys=keys)
        base = jax.tree_util.tree_map(lambda x: x[:, 0], res)
        nt, nm = jax.vmap(jax.vmap(vec.normalized_metrics,
                                   in_axes=(0, None, None)),
                          in_axes=(0, 0, 0))(res, base, sa.phase_mask)
        return np.asarray(nt), np.asarray(nm)

    single = env.compile(apps, seed=seeds)
    nt_one, nm_one = norms(env, single, list(range(len(socs))))

    buckets = stk.compile_apps_bucketed(apps, socs, seed=seeds,
                                        max_buckets=3, min_gain=0.0)
    groups = [g for g, _ in buckets]
    assert len(groups) > 1, "fixture must actually split"
    parts_t, parts_m = [], []
    for g, sa in buckets:
        nt, nm = norms(env.sublanes(g), sa, list(g))
        parts_t.append(nt)
        parts_m.append(nm)
    nt_re = stk.reassemble_lanes(groups, parts_t)
    nm_re = stk.reassemble_lanes(groups, parts_m)
    np.testing.assert_array_equal(nt_re, nt_one)
    np.testing.assert_array_equal(nm_re, nm_one)


def test_sweep_one_call_pair_per_bucket_and_reassembly():
    """A small end-to-end sweep: exactly one train + one eval call per
    bucket, margins finite, NON_COH row normalizes to exactly 1."""
    samples = dse.sample_socs(11, 6)
    out = dse.run_sweep(samples, iters=2, n_phases=2, max_buckets=3,
                        min_gain=0.0)
    calls = out["calls"]
    assert calls["train"] == calls["n_buckets"] <= 3
    assert calls["eval"] == calls["n_buckets"]
    assert sorted(i for g in out["groups"] for i in g) == list(range(6))
    nt, nm = out["norm_time"], out["norm_mem"]
    assert nt.shape == (6, len(dse.EVAL_FAMILIES))
    np.testing.assert_array_equal(nt[:, 0], np.ones(6))  # NON_COH row
    np.testing.assert_array_equal(nm[:, 0], np.ones(6))
    for v in out["margins"].values():
        assert np.isfinite(v).all()
    assert out["waste"]["padded_volume_bucketed"] \
        <= out["waste"]["padded_volume_single_call"]
    rank = out["axis_ranking"]["speedup_vs_noncoh"]
    assert len(rank["ranked_coefficients"]) == len(dse.FEATURE_AXES)


def test_sweep_results_independent_of_bucket_count():
    """Deterministic-family per-SoC numbers must not depend on how the
    sweep was bucketed (per-config seeds drive keys and striping, and
    padding rows are inert).  Keyed families (random, cohmeleon) redraw
    their pre-sampled noise when the padded scan length changes — those
    columns are only required to stay finite and in range."""
    samples = dse.sample_socs(12, 5)
    one = dse.run_sweep(samples, iters=2, n_phases=2, max_buckets=1)
    many = dse.run_sweep(samples, iters=2, n_phases=2, max_buckets=3,
                         min_gain=0.0)
    assert len(many["groups"]) > 1
    det = [i for i, f in enumerate(dse.EVAL_FAMILIES)
           if f.startswith("fixed") or f == "manual"]
    np.testing.assert_array_equal(one["norm_time"][:, det],
                                  many["norm_time"][:, det])
    np.testing.assert_array_equal(one["norm_mem"][:, det],
                                  many["norm_mem"][:, det])
    for out in (one, many):
        assert np.isfinite(out["norm_time"]).all()
        assert (out["norm_time"] > 0).all()


def test_rank_axes_recovers_a_planted_signal():
    samples = dse.sample_socs(0, 48)
    y = np.asarray([0.5 * s.axes["no_l2_frac"] - 0.05 for s in samples])
    out = dse.rank_axes(samples, {"planted": y})
    top = out["planted"]["ranked_coefficients"][0]
    assert top[0] == "no_l2_frac" and top[1] > 0
    assert out["planted"]["r2"] > 0.99


def test_budget_dataclass_roundtrip():
    b = dataclasses.replace(DEFAULT_BUDGET, max_area=10.0)
    assert b.max_area == 10.0 and DEFAULT_BUDGET.max_area != 10.0
