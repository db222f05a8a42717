"""What every cell shares: finding its files, building its SoCs, the timed
window, the result line.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); the mix names the driver that runs its verb
(``drivers/<driver>.py``), and each metric is read by ``metrics/<name>.py``.
A later cell adds files and ``BENCHMARK.json`` entries and edits none.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
import json
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
SPANS = ("window", "call", "dispatch", "block", "to_host")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(REPO / "BENCHMARK.json")


def cell(name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic) of the named cell."""
    for w in manifest()["workloads"]:
        if w["name"] == name:
            return (w, load_json(HERE / "configs" / f"{w['config']}.json"),
                    load_json(HERE / "traffic" / f"{w['traffic']}.json"))
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def driver_module(traffic: dict):
    return importlib.import_module(f"drivers.{traffic['driver']}")


def metric_module(name: str):
    return importlib.import_module(f"metrics.{name}")


def cell_metrics(name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in manifest()[kind]
            if name in m.get("workloads", [name])]


# ------------------------------------------------------------------ seeds
def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent numpy stream per (run seed, purpose, index)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), *stream]))


def raw_keys(seed: int, shape: tuple, *stream: int) -> np.ndarray:
    """Threefry keys (any uint32 pair is one) drawn from the run seed."""
    return rng(seed, *stream).integers(
        0, 1 << 32, size=(*shape, 2), dtype=np.uint64).astype(np.uint32)


# ------------------------------------------------------------------ build
def soc_configs(cfg: dict) -> list:
    """The program's SoCConfig for each lane, from the file's numbers."""
    from repro.soc.config import MemTimings, SoCConfig

    out = []
    for name, _ in cfg["lanes"]:
        s = cfg["socs"][name]
        out.append(SoCConfig(
            name=name, n_accs=s["n_accs"], noc_rows=s["noc_rows"],
            noc_cols=s["noc_cols"], n_cpus=s["n_cpus"],
            n_mem_tiles=s["n_mem_tiles"],
            llc_slice_bytes=s["llc_slice_bytes"], l2_bytes=s["l2_bytes"],
            accelerators=tuple(s["accelerators"]),
            no_private_cache=tuple(s["no_private_cache"]),
            timings=MemTimings(**cfg["timings"])))
    return out


def plain_socs(cfg: dict) -> list[dict]:
    """Each lane's SoC as plain numbers, for the reference."""
    return [dict(cfg["socs"][name], timings=cfg["timings"])
            for name, _ in cfg["lanes"]]


@dataclasses.dataclass
class Lanes:
    """The cell's SoC lanes: simulators, applications, program env."""

    sims: list
    env: object             # repro.soc.stacked.StackedVecEnv
    train_apps: list


def build_lanes(cfg: dict) -> Lanes:
    from repro.soc.apps import make_application
    from repro.soc.des import SoCSimulator
    from repro.soc.stacked import StackedVecEnv

    sims = [SoCSimulator(soc, seed=cfg["profile_seed"], flavor=fl)
            for soc, (_, fl) in zip(soc_configs(cfg), cfg["lanes"])]
    a = cfg["train_app"]
    apps = [make_application(s.soc, seed=a["seed"], n_phases=a["n_phases"])
            for s in sims]
    return Lanes(sims=sims, env=StackedVecEnv.from_simulators(sims),
                 train_apps=apps)


def eval_apps(cfg: dict, sims) -> list:
    from repro.soc.apps import make_application, make_case_study_app

    a = cfg["eval_app"]
    return [make_case_study_app(s.soc, seed=a["seed"],
                                loops=a["case_study_loops"])
            if name in a["case_study"] else
            make_application(s.soc, seed=a["seed"], n_phases=a["n_phases"])
            for s, (name, _) in zip(sims, cfg["lanes"])]


def tile_seeds(seed: int, n: int, stream: int) -> list[int]:
    """Tile-striping seeds: they move stripes, never a schedule's length."""
    return [int(x) for x in rng(seed, stream).integers(0, 1 << 31, n)]


# ----------------------------------------------------------------- window
@dataclasses.dataclass
class Call:
    start: float
    dispatched: float
    end: float
    invocations: int


@dataclasses.dataclass
class Window:
    start: float
    calls: list

    @property
    def end(self) -> float:
        return self.calls[-1].end

    @property
    def seconds(self) -> float:
        return self.end - self.start


def run_window(driver, seconds: float, annotate) -> Window:
    """Whole entry calls back to back until ``seconds`` have passed.

    Each call is dispatched, blocked on and (where the verb hands its
    result to the host) pulled; the next starts after.  ``annotate(name)``
    gives a context manager per span."""
    import jax

    calls = []
    with annotate("window"):
        t_start = time.perf_counter()
        i = 0
        while True:
            t0 = time.perf_counter()
            with annotate("call"):
                with annotate("dispatch"):
                    out = driver.dispatch(i)
                t1 = time.perf_counter()
                with annotate("block"):
                    jax.block_until_ready(out)
                if driver.pulls:
                    with annotate("to_host"):
                        driver.pull(i, out)
            t2 = time.perf_counter()
            calls.append(Call(t0, t1, t2, driver.invocations_per_call))
            i += 1
            if t2 - t_start >= seconds:
                break
    return Window(t_start, calls)


class CompileCounter:
    """Counts, while on, the programs JAX lowers, compiles or loads from
    its persistent cache (``count``), and apart from them the jaxprs it
    traces (``traces``: an eager ``vmap`` traces on every call without
    building a program)."""

    BUILD = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring as mon

        self.on = False
        self.reset()

        def listener(event, duration, *_a, **_k):
            if not self.on:
                return
            if event in self.BUILD:
                self.events.append(event)
                self.seconds[event.rsplit("/", 1)[-1]] += duration
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1

        mon.register_event_duration_secs_listener(listener)

    def reset(self):
        self.events: list[str] = []
        self.seconds = collections.defaultdict(float)
        self.traces = 0

    @property
    def count(self) -> int:
        return len(self.events)


def sample_outputs(seed: int, outputs: list, n: int) -> dict:
    """A seeded sample of ``n`` of the window's outputs, pulled to the
    host (``{call index: output}``); the device copies are dropped."""
    import jax

    picks = sorted(int(i) for i in rng(seed, 9).permutation(
        len(outputs))[:n])
    sampled = {i: jax.device_get(outputs[i]) for i in picks}
    outputs.clear()
    return sampled


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))
