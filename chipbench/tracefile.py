"""Reduction of a profiler trace of the window to device and host numbers.

``load`` reads the ``.xplane.pb`` the profiler wrote: the device planes'
operation events and the benchmark's own host spans (``harness.SPANS``),
all on the profiler's clock.  ``reduce`` turns those plain event lists
into the busy time per device, the operations that took most device time,
and the longest idle gaps labelled by the host span they fell in.  The
arithmetic works on plain lists so that a small synthetic trace can check
it (``tests/test_chipbench_trace.py``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from harness import SPANS

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU|CPU):(\d+)$")
OP_LINES = ("XLA Ops",)


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, n_devices: int):
    """(device events {id: [(name, start_ns, dur_ns)]}, host spans
    [(name, start_ns, dur_ns)], line names per device plane)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, lines = {}, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(2)) < n_devices:
            dev = int(m.group(2))
            lines[dev] = [ln.name for ln in plane.lines]
            evs = []
            for ln in plane.lines:
                if ln.name in OP_LINES:
                    evs += [(e.name, float(e.start_ns), float(e.duration_ns))
                            for e in ln.events]
            devices[dev] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in ln.events if e.name in SPANS]
    return devices, spans, lines


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def self_times(events):
    """{name: seconds} of each op's own time (nested ops subtracted)."""
    out = defaultdict(float)
    stack = []                        # (name, start, end, child_time)
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and stack[-1][2] <= s:
            n0, s0, e0, c0 = stack.pop()
            out[n0] += (e0 - s0 - c0) * 1e-9
            if stack:
                stack[-1][3] += e0 - s0
        stack.append([name, s, e, 0.0])
    while stack:
        n0, s0, e0, c0 = stack.pop()
        out[n0] += (e0 - s0 - c0) * 1e-9
        if stack:
            stack[-1][3] += e0 - s0
    return dict(out)


def _host_activity(lo, hi, spans):
    """What the host was doing over [lo, hi): the innermost span kind with
    the most overlap; time inside ``call`` but outside its children is
    host work of the loop itself."""
    cover = defaultdict(float)
    for name, s, d in spans:
        ov = min(hi, s + d) - max(lo, s)
        if ov > 0 and name != "window":
            cover[name] += ov
    inner = {k: v for k, v in cover.items() if k != "call"}
    if inner:
        return max(inner, key=inner.get)
    return "call" if cover else "between_calls"


def reduce(devices: dict, spans: list, top: int = 10) -> dict:
    """Busy seconds per device inside the ``window`` span, the window's
    length, the top device ops and the longest idle gaps."""
    win = [(s, s + d) for n, s, d in spans if n == "window"]
    if not win:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = win[0]
    busy, gaps, ops = {}, [], defaultdict(float)
    for dev, evs in devices.items():
        u = clip(union((s, s + d) for _, s, d in evs), lo, hi)
        busy[dev] = sum(e - s for s, e in u) * 1e-9
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
        inside = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                  for n, s, d in evs if s + d > lo and s < hi]
        for n, t in self_times(inside).items():
            ops[n] += t / max(len(devices), 1)
    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy,
        "device_ops": sorted(([n, t] for n, t in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[_host_activity(a, b, spans), g * 1e-9]
                      for g, a, b in gaps[:top]],
        "n_calls": sum(1 for n, _, _ in spans if n == "call"),
    }
