"""The benchmark's manifest, its files found by name, the work count and
the trace reduction -- checks that need no chip and no program run."""
from __future__ import annotations

import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import tracefile  # noqa: E402
import work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = harness.manifest()


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in MANIFEST["configs"]]
             + [w["name"] for w in MANIFEST["workloads"]]
             + [m["name"] for m in MANIFEST["end_to_end"]
                + MANIFEST["per_layer"]]
             + [w[k] for w in MANIFEST["workloads"]
                for k in ("config", "traffic")]
             + [k for c in MANIFEST["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads"):
        entries = [e["name"] for e in MANIFEST[kind]]
        assert len(entries) == len(set(entries))
    metrics = [m["name"] for m in MANIFEST["end_to_end"]
               + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(pairs) // 2)


def test_every_moves_is_reported_by_its_cells():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        target = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in target.get("workloads", cells), (m["name"], c)
    for c in cells:
        reported = [m["name"] for m in harness.cell_metrics(c, "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2, c
        assert harness.cell_metrics(c, "per_layer"), c


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_files_are_found_by_name(cell):
    entry, cfg, traffic = harness.cell(cell)
    assert cfg["name"] == entry["config"]
    conf = next(c for c in MANIFEST["configs"] if c["name"] == cfg["name"])
    assert (harness.REPO / conf["file"]).is_file()
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    mod = harness.driver_module(traffic)
    assert hasattr(mod, "Driver") and hasattr(mod, "LIMITS")
    for kind in ("end_to_end", "per_layer"):
        for m in harness.cell_metrics(cell, kind):
            assert callable(harness.metric_module(m["name"]).read)


@pytest.mark.parametrize("n_threads,n_tiles", [(1, 2), (6, 4), (12, 4)])
def test_work_counts_the_step_row_widths(n_threads, n_tiles):
    """Bytes: the packed input rows, the trace row and one Q-row read and
    written, as the program's own row layout has them."""
    import jax.numpy as jnp
    from repro.kernels.soc_step.ref import YCOLS, StepInputs, pack_inputs

    s, a = 3, work.N_ACTIONS
    f32 = jnp.zeros((s,), jnp.float32)
    xs = StepInputs(
        acc_id=jnp.zeros((s,), jnp.int32), footprint=f32,
        tiles=jnp.zeros((s, n_tiles), bool), thread=jnp.zeros((s,),
                                                              jnp.int32),
        fresh=jnp.zeros((s,), bool), others=jnp.zeros((s, n_threads), bool),
        valid=jnp.zeros((s,), bool), pre_mode=jnp.zeros((s,), jnp.int32),
        profile=jnp.zeros((s, work.PROFILE_WIDTH)),
        avail=jnp.zeros((s, a), bool), eps=f32, alpha=f32, u_explore=f32,
        g_pick=jnp.zeros((s, a)), g_tie=jnp.zeros((s, a)))
    xf, xi = pack_inputs(xs)
    by, ops = work.per_invocation(n_threads, n_tiles)
    assert by == 4 * (xf.shape[1] + xi.shape[1] + len(YCOLS) + 2 * a)
    assert ops == 8 * n_threads * n_tiles + 6 * a + work.SCALAR_OPS
    by_s, ops_s = work.per_invocation(n_threads, n_tiles, serve=True,
                                      queue_cap=8)
    assert by_s == by + 4 * (work.SERVE_COLS - work.TRACE_COLS + 3)
    assert ops_s == ops + 64


def test_roofline_names_its_bound():
    peaks = {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12}
    assert work.roofline_seconds(2e9, 1e9, peaks) == (2.0, "bytes")
    assert work.roofline_seconds(1e6, 3e12, peaks) == (3.0, "operations")


def test_peaks_table_refuses_an_unknown_kind():
    import peaks
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("TPU v99")


def test_trace_reduction_on_a_synthetic_trace():
    """Window 0-100 ns; device 0 busy 10-40 (op a, with op b nested 20-30)
    and 60-70 (op c); device 1 busy 0-100.  Host: a dispatch 40-55 and a
    block 55-60, inside one call."""
    spans = [("window", 0.0, 100.0), ("call", 0.0, 100.0),
             ("dispatch", 40.0, 15.0), ("block", 55.0, 5.0)]
    devices = {0: [("a", 10.0, 30.0), ("b", 20.0, 10.0), ("c", 60.0, 10.0),
                   ("late", 150.0, 10.0)],
               1: [("d", -10.0, 120.0)]}
    red = tracefile.reduce(devices, spans)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"][0] == pytest.approx(40e-9)
    assert red["busy_s"][1] == pytest.approx(100e-9)
    ops = dict(red["device_ops"])
    assert ops["a"] == pytest.approx(20e-9 / 2)      # self time, mean
    assert ops["b"] == pytest.approx(10e-9 / 2)
    assert ops["d"] == pytest.approx(100e-9 / 2)
    assert "late" not in ops
    gaps = red["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9, 10e-9])
    # 70-100 overlaps no child span: the host was in its own loop; 40-60
    # is mostly dispatch; 0-10 only the call.
    assert [g[0] for g in gaps] == ["call", "dispatch", "call"]
    assert red["n_calls"] == 1


def test_trace_union_and_self_times():
    assert tracefile.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    st = tracefile.self_times([("p", 0.0, 10.0), ("q", 2.0, 3.0),
                               ("r", 6.0, 2.0)])
    assert st == pytest.approx({"p": 5e-9, "q": 3e-9, "r": 2e-9})
