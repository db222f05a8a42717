"""Whole runs of each verb's driver, small and on the CPU.

A rehearsal per verb (the window, the metrics, the reference agreeing
with the program, the bfloat16 control failing), the command's refusal
without a TPU, and runs whose timed path is broken underneath -- each
fault must turn ``correct`` false.  Sizes come from the test, never from
the command line.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import faults  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402

SEED = 4_294_967_311          # more than 32 bits
SMALL = {"table4-train": {"agents_per_weighting": 1, "iterations": 1},
         "table4-eval": {"agents_per_weighting": 1, "iterations": 1},
         "soc1-serve": {"iterations": 1, "requests_per_chunk": 128,
                        "compare_chunks": 2}}
SECONDS = 0.3


def _run(cell, patch=None, control=False, seed=SEED):
    return run.run_cell(cell, seed, SECONDS, False, require_tpu=False,
                        scale=SMALL[cell], control=control,
                        log=lambda *_: None, patch=patch)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_rehearsal_agrees_with_reference_and_control_fails(cell):
    res = _run(cell, control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    names = {m["name"] for m in harness.cell_metrics(cell, "end_to_end")}
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())
    limits = {k: v["limit"] for k, v in res["checks"].items()}
    assert any(res["control_checks"][k] > limits[k] for k in limits), res
    assert list(res)[-1] == "checks"


def test_seed_moves_inputs_not_shapes():
    from drivers.train import Training

    _, cfg, traffic = harness.cell("table4-train")
    lanes = harness.build_lanes(cfg)
    small = dict(traffic, **SMALL["table4-train"])
    a = Training(cfg, small, 1, 1, lanes=lanes)
    b = Training(cfg, small, SEED, 1, lanes=lanes)
    sa, sb = a.stacked[0].schedule, b.stacked[0].schedule
    assert sa.tiles.shape == sb.tiles.shape and a.n_steps == b.n_steps
    assert not np.array_equal(np.asarray(sa.tiles), np.asarray(sb.tiles))
    assert not np.array_equal(harness.raw_keys(1, (2,), 2, 0),
                              harness.raw_keys(SEED, (2,), 2, 0))


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "table4-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


# ------------------------------------------------------------ faults
FAULT_CASES = [(cell, name) for cell in sorted(SMALL)
               for name in faults.FAULTS[harness.cell(cell)[2]["driver"]]]


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    scale = dict(SMALL[cell])
    if cell != "soc1-serve":
        scale["agents_per_weighting"] = 2
    else:
        scale["compare_chunks"] = 3
    patch = faults.FAULTS[harness.cell(cell)[2]["driver"]][fault]
    res = run.run_cell(cell, SEED, SECONDS, False, require_tpu=False,
                       scale=scale, log=lambda *_: None, patch=patch)
    assert not res["correct"], json.dumps(res["checks"])


FOUR_CHIP = r'''
import json, sys
sys.path.insert(0, {bench!r})
import run  # puts the program on the path
import jax
import faults
import harness
from drivers import train

cfg = harness.load_json(harness.HERE / "configs" / "table4.json")
traffic = harness.load_json(harness.HERE / "traffic" / "train_x4.json")
scale = {{"agents_per_weighting": 2, "iterations": 1}}
out = {{}}
for name, patch in (("sound", None),
                    ("fault", faults.FAULTS["train"]["chip_share_left_out"])):
    d = train.Driver(cfg, traffic, {seed}, jax.devices(), scale)
    d.warm()
    if patch:
        patch(d)
    window = harness.run_window(d, 0.3, lambda _: __import__(
        "contextlib").nullcontext())
    d.collect(len(window.calls))
    checks = d.check()
    out[name] = all(checks[k] <= v for k, v in train.LIMITS.items())
out["devices"] = len(d.mesh.devices.flat)
print(json.dumps(out))
'''


def test_lane_mesh_training_on_four_virtual_devices():
    """The four-chip traffic (``train_x4``) through the lane mesh on 4 CPU
    devices: agrees with the reference, and a chip's share of the agents
    left out is caught."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", FOUR_CHIP.format(bench=str(BENCH),
                                                 seed=SEED)],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "fault": False, "devices": 4}
