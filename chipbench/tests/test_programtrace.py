"""The readers of the program's own spans and scopes, checked exactly on a
small synthetic trace written as a real ``.xplane.pb``.

Window 0-100 ns holding two calls (0-50, 50-100).  Host: ``cohm.prep``
5-10 and 55-57, ``cohm.launch`` 10-15 and 57-60.  Device 0: a ``while``
with no scope path 12-20 around two pre-sampling body ops, the step
20-40, an unscoped tail 40-45, a second pathless ``while`` 60-90 around
the step 60-85 and an unscoped gather, and a step op at 150-160 after
the window.  Device 1: the step 0-80.  As on the chip, the scope path
is a stat of each op's event metadata.
"""
from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import programtrace  # noqa: E402
from metrics import (idle_in_program_pct, launch_ms, prep_ms,  # noqa: E402
                     presample_busy_pct, step_busy_pct)

PRE = "jit(serve)/cohm_presample/while/body"
STEP = "jit(one)/vmap(vmap(cohm_step))/jit(soc_step_episode)/pallas_call:"
HOST = [("window", 0, 100), ("call", 0, 50), ("call", 50, 50),
        ("cohm.prep", 5, 5), ("cohm.launch", 10, 5),
        ("cohm.prep", 55, 2), ("cohm.launch", 57, 3),
        ("dispatch", 5, 10)]
DEVICES = {0: [("", 12, 8), (PRE + "/dynamic_slice:", 14, 2),
               (PRE + "/dynamic_update_slice:", 17, 2),
               (STEP, 20, 20), ("jit(one)/tail:", 40, 5),
               ("", 60, 30), (STEP, 60, 25), ("jit(one)/gather:", 86, 2),
               (STEP, 150, 10)],
           1: [(STEP, 0, 80)]}
T0 = 1_000_000                    # the lines' timestamp, ns

BUSY0 = (45 - 12) + (90 - 60)     # device 0's busy time in the window
IDLE0 = 100 - BUSY0


def _plane(pid, name, line, events, host=False):
    """Text proto of one XPlane: one line, one metadata entry per event,
    a device op's scope path in its metadata's ``tf_op`` stat."""
    evs, meta = [], []
    for i, (n, start, dur) in enumerate(events, 1):
        evs.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000}"
                   f" duration_ps: {dur * 1000} }}")
        stat = ("" if host or not n else
                f' stats {{ metadata_id: 1 str_value: "{n}" }}')
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n if host else f"op.{i}"}"{stat} }} }}')
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: {T0} {" ".join(evs)} }} '
            f'{" ".join(meta)} stat_metadata {{ key: 1 value {{ id: 1 '
            f'name: "{programtrace.SCOPE_STAT}" }} }} }}')


def write_trace(path: Path, host=HOST, devices=DEVICES):
    from jax.profiler import ProfileData

    planes = [_plane(1, "/host:CPU", "python", host, host=True)]
    planes += [_plane(10 + d, f"/device:TPU:{d}", "XLA Ops", evs)
               for d, evs in devices.items()]
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        " ".join(planes)))


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(programtrace, "TRACE_DIR", str(tmp_path))
    programtrace._load.cache_clear()
    return tmp_path


def _run(window_s=100e-9, devices=(0, 1)):
    return types.SimpleNamespace(reduction={
        "window_s": window_s, "busy_s": {d: 1.0 for d in devices}})


def test_readers_on_a_synthetic_trace(trace_dir):
    write_trace(trace_dir / "cell" / "host.xplane.pb")
    run = _run()
    assert prep_ms.read(run) == pytest.approx((5 + 2) / 2 * 1e-6)
    assert launch_ms.read(run) == pytest.approx((5 + 3) / 2 * 1e-6)
    # The first while's body is all pre-sampling, so the whole loop is;
    # the second holds the step and an unscoped gather, so it is neither.
    assert step_busy_pct.read(run) == pytest.approx(
        (100.0 * 45 / BUSY0 + 100.0) / 2)
    assert presample_busy_pct.read(run) == pytest.approx(
        (100.0 * 8 / BUSY0 + 0.0) / 2)
    # Device 0 idles 0-12, 45-60, 90-100; program spans cover 5-12 and
    # 55-60 of that.  Device 1 idles 80-100 with no program span open.
    assert idle_in_program_pct.read(run) == pytest.approx(
        (100.0 * 12 / IDLE0 + 0.0) / 2)


def test_only_the_cells_devices_are_read(trace_dir):
    write_trace(trace_dir / "cell" / "host.xplane.pb")
    assert step_busy_pct.read(_run(devices=(0,))) == pytest.approx(
        100.0 * 45 / BUSY0)


@pytest.mark.parametrize("reader", [prep_ms, launch_ms, step_busy_pct,
                                    presample_busy_pct, idle_in_program_pct])
def test_readers_return_none_without_program_spans(trace_dir, reader):
    host = [s for s in HOST if not s[0].startswith("cohm.")]
    write_trace(trace_dir / "cell" / "host.xplane.pb", host=host)
    assert reader.read(_run()) is None


@pytest.mark.parametrize("reader", [prep_ms, launch_ms, step_busy_pct,
                                    presample_busy_pct, idle_in_program_pct])
def test_readers_return_none_when_the_window_differs(trace_dir, reader):
    write_trace(trace_dir / "cell" / "host.xplane.pb")
    assert reader.read(_run(window_s=101e-6)) is None
    assert reader.read(types.SimpleNamespace(reduction=None)) is None


def test_readers_return_none_without_a_trace(trace_dir):
    assert prep_ms.read(_run()) is None


def test_scope_is_one_element_of_the_path():
    assert programtrace.in_scope(STEP, "cohm_step")
    assert programtrace.in_scope("jit(f)/cohm_step/add", "cohm_step")
    assert not programtrace.in_scope(PRE, "cohm_step")
    assert not programtrace.in_scope("jit(f)/cohm_step_tail/x", "cohm_step")
    assert not programtrace.in_scope("", "cohm_step")


def test_a_pathless_op_takes_the_common_prefix_of_its_nested_ops():
    got = programtrace.inherit_paths([
        ("", 0, 10), ("a/b/c:", 1, 2), ("a/b/d:", 4, 2), ("x/y:", 20, 1),
        ("", 30, 5), ("", 40, 5), ("a/e:", 41, 1), ("q:", 43, 5)])
    assert sorted(got, key=lambda e: e[1]) == [
        ("a/b", 0, 10), ("a/b/c:", 1, 2), ("a/b/d:", 4, 2), ("x/y:", 20, 1),
        ("", 30, 5), ("a/e:", 40, 5), ("a/e:", 41, 1), ("q:", 43, 5)]


def test_overlap_of_interval_lists():
    assert programtrace.overlap([(0, 5), (10, 20)], [(3, 12), (15, 30)]) == 9
    assert programtrace.overlap([], [(0, 1)]) == 0


def test_no_program_span_reuses_a_benchmark_span_name():
    assert not any(n.startswith(programtrace.PREFIX) for n in harness.SPANS)
