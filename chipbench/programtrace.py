"""The program's own spans and name scopes in this run's profiler trace.

The simulator marks its host work per entry call with profiler spans
(``cohm.prep``: host preparation before the compiled program is called;
``cohm.launch``: the jit cache lookup and the call) and the parts of its
device programs with name scopes (``cohm_presample``: noise, decay,
fault and arrival pre-sampling; ``cohm_step``: the episode or serving
step, kernel or XLA scan).  ``read(run)`` finds the trace ``run.py``
wrote for this run and returns a ``ProgramTrace``, or ``None`` where the
trace holds no program span (a program without them) or its ``window``
is not the one ``run.reduction`` was made from.

A device op's scope path is the ``tf_op`` stat of its event's metadata
(``jit(serve)/cohm_presample/while/body/dynamic_slice:``), which
``jax.profiler.ProfileData`` does not expose: ``scope_paths`` reads it
from the ``.xplane.pb`` itself.  XLA leaves a ``while`` op without one;
such an op takes the common prefix of the paths of the ops nested in it,
so the arrival scan's loop counts as pre-sampling and the training loop
around every scope counts as none.  The arithmetic works on plain lists,
so that a small synthetic trace can check it
(``tests/test_programtrace.py``).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import re

from tracefile import DEVICE_PLANE, OP_LINES, clip, newest_xplane, union

TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".out", "trace")
PREFIX = "cohm."
HOST_SPANS = ("window", "call")
SCOPE_STAT = "tf_op"
WINDOW_TOLERANCE_S = 1e-6


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` is one element of a scope path such as
    ``jit(f)/vmap(cohm_step)/pallas_call``."""
    return re.search(rf"(?<![\w.]){re.escape(scope)}(?![\w.])",
                     path) is not None


@dataclasses.dataclass
class ProgramTrace:
    """``spans``: host (name, start_ns, dur_ns) of ``window``, ``call``
    and every ``cohm.*`` span; ``ops``: per device, (scope path,
    start_ns, dur_ns) of each operation event."""

    spans: list
    ops: dict
    _busy_cache: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def window(self) -> tuple[float, float]:
        s, d = next((s, d) for n, s, d in self.spans if n == "window")
        return s, s + d

    def _host(self, pred):
        lo, hi = self.window
        return clip(union((s, s + d) for n, s, d in self.spans if pred(n)),
                    lo, hi)

    def span_ms_per_call(self, name: str) -> float | None:
        """Mean over the window's calls of the time a ``name`` span was
        open inside the call."""
        lo, hi = self.window
        spans = self._host(lambda n: n == name)
        calls = clip([(s, s + d) for n, s, d in self.spans if n == "call"],
                     lo, hi)
        if not spans or not calls:
            return None
        return sum(overlap(spans, [c]) for c in calls) / len(calls) * 1e-6

    def _busy(self, dev, scope=None):
        """Merged op intervals of ``dev`` in the window, those under
        ``scope`` only where it is given."""
        if (dev, scope) not in self._busy_cache:
            lo, hi = self.window
            ops = self.ops[dev]
            if scope is not None:
                inside = {p for p in {p for p, _, _ in ops}
                          if in_scope(p, scope)}
                ops = [op for op in ops if op[0] in inside]
            self._busy_cache[dev, scope] = clip(
                union((s, s + d) for _, s, d in ops), lo, hi)
        return self._busy_cache[dev, scope]

    def scope_busy_pct(self, scope: str) -> float | None:
        """100 x the window's device time under ``scope`` over its device
        busy time, mean over devices."""
        shares = []
        for dev in self.ops:
            busy = _length(self._busy(dev))
            if busy > 0:
                shares.append(100.0 * _length(self._busy(dev, scope)) / busy)
        return sum(shares) / len(shares) if shares else None

    def idle_in_program_pct(self) -> float | None:
        """100 x the window's device-idle time during which a program span
        was open over all its device-idle time, mean over devices."""
        lo, hi = self.window
        program = self._host(lambda n: n.startswith(PREFIX))
        shares = []
        for dev in self.ops:
            edges = [lo] + [x for iv in self._busy(dev) for x in iv] + [hi]
            idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
            if idle:
                shares.append(100.0 * overlap(idle, program) / _length(idle))
        return sum(shares) / len(shares) if shares else None


# ---------------------------------------------------- the .xplane.pb itself
# Field numbers of tsl/profiler/protobuf/xplane.proto that hold the scope
# paths: XSpace.planes; XPlane.name, .event_metadata, .stat_metadata (maps:
# entry key 1, value 2); X{Event,Stat}Metadata.name; XEventMetadata.stats;
# XStat.metadata_id, .str_value, .ref_value.
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_META, PLANE_STAT_META = 2, 4, 5
META_NAME, EVENT_META_STATS = 2, 5
STAT_META_ID, STAT_STR, STAT_REF = 1, 5, 7


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an
    int for varints, a memoryview for length-delimited fields."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _map(entries):
    out = {}
    for entry in entries:
        f = dict(_fields(entry))
        out[f.get(1, 0)] = f.get(2, b"")
    return out


def scope_paths(path: str, devices) -> dict:
    """{device: {op event name: scope path}} from the ``SCOPE_STAT`` stat
    of each device op's event metadata.  A name two metadata entries give
    different paths maps to no path."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != SPACE_PLANES:
            continue
        name, events, stats = "", [], []
        for n, v in _fields(plane):
            if n == PLANE_NAME:
                name = bytes(v).decode()
            elif n == PLANE_EVENT_META:
                events.append(v)
            elif n == PLANE_STAT_META:
                stats.append(v)
        m = DEVICE_PLANE.match(name)
        if not m or int(m.group(2)) not in devices:
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(META_NAME, b"")).decode()
                      for k, v in _map(stats).items()}
        scope_id = next((k for k, v in stat_names.items()
                         if v == SCOPE_STAT), None)
        paths = {}
        for meta in _map(events).values():
            ev_name, scope = "", ""
            for n, v in _fields(meta):
                if n == META_NAME:
                    ev_name = bytes(v).decode()
                elif n == EVENT_META_STATS:
                    st = dict(_fields(v))
                    if st.get(STAT_META_ID) == scope_id:
                        scope = (bytes(st[STAT_STR]).decode()
                                 if STAT_STR in st
                                 else stat_names.get(st.get(STAT_REF), ""))
            known = paths.setdefault(ev_name, scope)
            if known != scope:
                paths[ev_name] = ""
        out[int(m.group(2))] = paths
    return out


def inherit_paths(events):
    """(path, start, dur) events of one line, where an event with no path
    takes the common prefix of the paths of the events nested in it."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    starts = [s for _, s, _ in events]
    out = []
    for p, s, d in events:
        if not p:
            inner = events[bisect.bisect_left(starts, s):
                           bisect.bisect_right(starts, s + d)]
            nested = {q for q, s2, d2 in inner if q and s2 + d2 <= s + d}
            if nested:
                p = "/".join(os.path.commonprefix(
                    [q.split("/") for q in nested]))
        out.append((p, s, d))
    return out


def load(path: str, devices) -> ProgramTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    paths = scope_paths(path, devices)
    spans, ops = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(2)) in devices:
            dev = int(m.group(2))
            ops[dev] = []
            for ln in plane.lines:
                if ln.name in OP_LINES:
                    ops[dev] += inherit_paths([
                        (paths[dev].get(e.name, ""), float(e.start_ns),
                         float(e.duration_ns)) for e in ln.events])
        elif plane.name.startswith("/host:"):
            spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                      for ln in plane.lines for e in ln.events
                      if e.name in HOST_SPANS or e.name.startswith(PREFIX)]
    return ProgramTrace(spans, ops)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float, devices: tuple) -> ProgramTrace:
    """``load``, once per trace file for the five readers of a run."""
    return load(path, set(devices))


def read(run) -> ProgramTrace | None:
    red = run.reduction
    if red is None or not red["busy_s"]:
        return None
    try:
        path = newest_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    trace = _load(path, os.path.getmtime(path), tuple(sorted(red["busy_s"])))
    wins = [d for n, _, d in trace.spans if n == "window"]
    if (len(wins) != 1 or abs(wins[0] * 1e-9 - red["window_s"])
            > WINDOW_TOLERANCE_S
            or not any(n.startswith(PREFIX) for n, _, _ in trace.spans)):
        return None
    return trace
