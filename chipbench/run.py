"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's SoCs, applications and inputs from ``--seed``,
warms every program the window will run, and then the window calls the
cell's entry point back to back for ``--seconds``.  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  After the window the cell's outputs are
compared with the plain reference (``reference.py``); every compared
number is printed beside its limit.  The last line of standard output is
one JSON object.  The run exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache lives in ``chipbench/.jax_cache``
(``$JAX_COMPILATION_CACHE_DIR`` when that is set), traces under
``chipbench/.out``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunRecord:
    """What the metric readers read."""

    setup_s: float
    window: harness.Window
    driver: object
    reduction: dict | None
    peaks: dict | None
    notes: dict


def _configure_cache(jax) -> None:
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR",
                           str(HERE / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _annotator(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _trace_window(driver, seconds, trace_dir, n_devices):
    import jax
    import tracefile

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        window = harness.run_window(driver, seconds, _annotator(True))
    finally:
        jax.profiler.stop_trace()
    devices, spans, lines = tracefile.load(
        tracefile.newest_xplane(str(trace_dir)), n_devices)
    return window, tracefile.reduce(devices, spans), lines


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, scale: dict | None = None,
             control: bool = False, log=print, patch=None) -> dict:
    """One run of a cell; returns the result object (see module doc).

    ``scale`` overrides traffic sizes (tests run a cell small on the CPU);
    ``control`` adds the precision control's readings under
    ``control_checks``; ``patch(driver)``, applied after set-up, lets a
    test break the timed path underneath."""
    entry, cfg, traffic = harness.cell(workload)
    mod = harness.driver_module(traffic)
    import jax

    _configure_cache(jax)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {dev.platform}")
    if len(devices) < entry["chips"]:
        raise NoChip(f"cell {workload} needs {entry['chips']} chips, "
                     f"JAX found {len(devices)}")
    devices = devices[:entry["chips"]]
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(jax.devices())} used={len(devices)}")
    peaks = None
    if dev.platform == "tpu":
        import peaks as peaks_mod
        peaks = peaks_mod.lookup(dev.device_kind)

    counter = harness.CompileCounter()
    counter.on = True
    t_init = time.perf_counter()
    driver = mod.Driver(cfg, traffic, seed, devices, scale)
    t_built = time.perf_counter()
    driver.warm()
    setup_s = time.perf_counter() - T_START
    log(f"setup: jax_and_device_init_s={t_init - T_START:.3f} "
        f"host_prep_and_setup_calls_s={t_built - t_init:.3f} "
        f"warm_call_s={T_START + setup_s - t_built:.3f} of which "
        + " ".join(f"{k}={v:.3f}" for k, v in sorted(counter.seconds.items())))
    counter.reset()
    if patch is not None:
        patch(driver)

    counter.on = True
    reduction = lines = None
    if trace:
        window, reduction, lines = _trace_window(
            driver, seconds, HERE / ".out" / "trace" / workload,
            len(devices))
    else:
        window = harness.run_window(driver, seconds, _annotator(False))
    counter.on = False
    log(f"window: calls={len(window.calls)} seconds={window.seconds:.6f} "
        f"compiles_in_window={counter.count} "
        f"jaxpr_traces_in_window={counter.traces} setup_s={setup_s:.3f}")
    if counter.count:
        log(f"window compile events: {sorted(set(counter.events))}")
    memory = harness.peak_memory(devices)
    if reduction is not None:
        log(f"trace: planes' lines {lines}")
        for d, b in sorted(reduction["busy_s"].items()):
            log(f"trace: device {d} busy_s={b:.6f} "
                f"window_s={reduction['window_s']:.6f}")

    rec = RunRecord(setup_s, window, driver, reduction, peaks, {})
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in harness.cell_metrics(workload, kind):
        value = harness.metric_module(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for k, v in rec.notes.items():
        log(f"note: {k}={v}")

    driver.collect(len(window.calls))
    t_ref = time.perf_counter()
    checks = driver.check()
    t_ref = time.perf_counter() - t_ref
    log(f"reference: {t_ref:.3f} s")
    limits = mod.LIMITS
    result = {
        "correct": all(checks[k] <= limits[k] for k in limits),
        "attempted": len(window.calls),
        "failed": 0,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory},
    }
    if reduction is not None:
        busy = reduction["busy_s"]
        result["device"]["busy_s"] = (sum(busy.values()) / len(busy)
                                      if busy else 0.0)
        result["device"]["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["reference_s"] = t_ref
    if control:
        result["control_checks"] = driver.check(control=True)
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
