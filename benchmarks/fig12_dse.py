"""Beyond-paper Fig. 12: generative SoC design-space co-search.

The paper evaluates eight hand-written SoCs; this figure samples
hundreds of SoC architectures under a lumos-style area/bandwidth budget
(:func:`repro.soc.dse.sample_socs`), trains one Cohmeleon agent per SoC
and evaluates the full policy suite through k-way bucketed
``StackedVecEnv`` calls — at most ``max_buckets`` batched (train, eval)
call pairs for the WHOLE sweep, asserted below — and reports which
architectures, and which sampler axes, make learned coherence win
biggest (speedup and off-chip reduction vs the NON_COH baseline).

The committed report also records the sweep's padded-waste reduction
from k-way bucketing vs a single stacked call on the same sample, and
its steps/s, so future ``--check-regression``-style gates can compare
against it.

``--quick`` keeps the >= 200-SoC scale (the acceptance protocol) but
shrinks apps/iterations; it is the CI smoke job.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from benchmarks.common import csv_row, save_report
from repro.soc.config import DEFAULT_BUDGET
from repro.soc.dse import EVAL_FAMILIES, run_sweep, sample_socs

TOP_N = 10


def _per_soc_rows(samples, out) -> list[dict]:
    nt, nm = out["norm_time"], out["norm_mem"]
    n_fixed = len(EVAL_FAMILIES) - 3
    rows = []
    for i, s in enumerate(samples):
        rows.append({
            "name": s.config.name,
            "seed": s.seed,
            "axes": {k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in s.axes.items()},
            "cohmeleon": [float(nt[i, -1]), float(nm[i, -1])],
            "manual": [float(nt[i, -2]), float(nm[i, -2])],
            "fixed_mean": [float(nt[i, :n_fixed].mean()),
                           float(nm[i, :n_fixed].mean())],
            "best_fixed": [float(nt[i, :n_fixed].min()),
                           float(nm[i, :n_fixed].min())],
            "speedup_vs_noncoh":
                float(out["margins"]["speedup_vs_noncoh"][i]),
            "offchip_reduction_vs_noncoh":
                float(out["margins"]["offchip_reduction_vs_noncoh"][i]),
            "speedup_vs_best_fixed":
                float(out["margins"]["speedup_vs_best_fixed"][i]),
        })
    return rows


def run(quick: bool = False, n: int | None = None, max_buckets: int = 4,
        key: int = 0):
    n = n if n is not None else (200 if quick else 256)
    iters = 2 if quick else 3
    n_phases = 2 if quick else 3

    t0 = time.perf_counter()
    samples = sample_socs(key, n)
    out = run_sweep(samples, iters=iters, n_phases=n_phases,
                    max_buckets=max_buckets)
    us = (time.perf_counter() - t0) * 1e6 / n

    # Acceptance protocol: hundreds of SoCs, and the whole sweep is at
    # most ``max_buckets`` batched train/eval call pairs — one pair per
    # bucket, never one per SoC.
    calls = out["calls"]
    calls_ok = (calls["train"] == calls["n_buckets"]
                and calls["eval"] == calls["n_buckets"]
                and calls["n_buckets"] <= max_buckets)
    assert calls_ok, f"one train+eval call pair per bucket violated: {calls}"
    if quick or n >= 200:
        assert n >= 200, f"sweep must cover >= 200 SoCs, got {n}"

    margins = out["margins"]
    rows = _per_soc_rows(samples, out)
    order = np.argsort(-margins["speedup_vs_noncoh"])
    results = {
        "_engine": {
            "path": "vecenv-bucketed",
            "n_socs": n,
            "key": key,
            "iters": iters,
            "n_phases": n_phases,
            "max_buckets": max_buckets,
            "bucket_sizes": [len(g) for g in out["groups"]],
            "train_calls": calls["train"],
            "eval_calls": calls["eval"],
            "calls_ok": calls_ok,
        },
        "budget": dataclasses.asdict(DEFAULT_BUDGET),
        "waste": out["waste"],
        "_headline": {
            "mean_speedup_vs_noncoh":
                float(np.mean(margins["speedup_vs_noncoh"])),
            "mean_offchip_reduction_vs_noncoh":
                float(np.mean(margins["offchip_reduction_vs_noncoh"])),
            "mean_speedup_vs_fixed_mean":
                float(np.mean(margins["speedup_vs_fixed_mean"])),
            "frac_learned_beats_all_fixed":
                float(np.mean(margins["speedup_vs_best_fixed"] > 0)),
            "frac_learned_beats_noncoh":
                float(np.mean(margins["speedup_vs_noncoh"] > 0)),
        },
        "axis_ranking": out["axis_ranking"],
        "top_socs_by_learned_margin": [rows[i] for i in order[:TOP_N]],
        "bottom_socs_by_learned_margin": [rows[i] for i in order[-3:]],
        "per_soc": rows,
    }
    save_report("fig12_dse", results)

    head = results["_headline"]
    top_axis = out["axis_ranking"]["speedup_vs_noncoh"][
        "ranked_coefficients"][0]
    return csv_row(
        "fig12_dse", us,
        f"n_socs={n} buckets={calls['n_buckets']}/{max_buckets} "
        f"calls_ok={calls_ok} "
        f"speedup_vs_noncoh={head['mean_speedup_vs_noncoh'] * 100:.0f}% "
        f"offchip_red={head['mean_offchip_reduction_vs_noncoh'] * 100:.0f}% "
        f"waste={out['waste']['padded_waste_single_call'] * 100:.0f}%"
        f"->{out['waste']['padded_waste_bucketed'] * 100:.0f}% "
        f"top_axis={top_axis[0]}:{top_axis[1]:+.3f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--n", type=int, default=None,
                    help="sample count (default 256, 200 in --quick)")
    ap.add_argument("--max-buckets", type=int, default=4)
    ap.add_argument("--key", type=int, default=0)
    args = ap.parse_args()
    print(run(quick=args.quick, n=args.n, max_buckets=args.max_buckets,
              key=args.key))
