"""From a profiler trace to device numbers: busy and idle time, time per
compiled program, and the longest idle gaps with the host span the
benchmark had open in each.

``load`` reads an ``.xplane.pb`` with JAX's ``ProfileData`` into plain
event tuples; ``reduce`` works on those alone, so it is checked on a
small recorded trace without a chip.  Device planes are ``/device:TPU:n``;
busy time is the union of the ``XLA Ops`` events, and program time the
``XLA Modules`` events (a jitted function ``f`` runs as module
``jit_f``).  The window is the host span named ``window``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW = "window"
TOP = 10


@dataclass(frozen=True)
class Event:
    plane: str        # "device:<n>" or "host"
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over the devices
    programs: dict = field(default_factory=dict)  # name -> [seconds, count]
    device_ops: list = field(default_factory=list)   # [[name, s]], top
    idle_gaps: list = field(default_factory=list)    # [[span, s]], top


def load(path: Path, host_spans) -> list:
    """Events of the device planes' op and module lines, and the host
    spans named in ``host_spans``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (OPS, MODULES):
                    out.extend(Event(f"device:{m.group(1)}", line.name,
                                     e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend(Event("host", line.name, e.name, e.start_ns,
                                 e.duration_ns) for e in line.events
                           if e.name in host_spans)
    return out


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def program_name(module_event_name: str) -> str:
    """``jit__decode_paged_batched(123)`` -> ``jit__decode_paged_batched``"""
    return module_event_name.split("(")[0].strip()


def reduce(events: list) -> Reduced:
    """Device numbers over the window: from the start of the host span
    ``window`` to its end."""
    host = [e for e in events if e.plane == "host"]
    wins = [e for e in host if e.name == WINDOW]
    if not wins:
        raise ValueError("the trace has no host span named 'window'")
    lo = min(e.start_ns for e in wins)
    hi = max(e.end_ns for e in wins)
    dev = [e for e in events if e.plane.startswith("device:")
           and lo <= e.start_ns < hi]
    planes = sorted({e.plane for e in dev})
    busy, gaps = 0.0, []
    for p in planes:
        ops = [(e.start_ns, e.end_ns) for e in dev
               if e.plane == p and e.line == OPS]
        if not ops:
            ops = [(e.start_ns, e.end_ns) for e in dev
                   if e.plane == p and e.line == MODULES]
        merged = _union(ops, lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    programs: dict = {}
    for e in dev:
        if e.line == MODULES:
            acc = programs.setdefault(program_name(e.name), [0.0, 0])
            acc[0] += e.dur_ns * 1e-9
            acc[1] += 1
    n = max(1, len(planes))
    spans = [e for e in host if e.name != WINDOW]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / n,
        programs=programs,
        device_ops=[[k, v[0]] for k, v in sorted(
            programs.items(), key=lambda kv: -kv[1][0])[:TOP]],
        idle_gaps=[[_span_at(spans, (s + e) / 2), (e - s) * 1e-9]
                   for s, e in top_gaps])


def _span_at(spans, t):
    """The innermost host span open at ``t``, or ``none``."""
    open_ = [e for e in spans if e.start_ns <= t < e.end_ns]
    return min(open_, key=lambda e: e.dur_ns).name if open_ else "none"


def program_time(red: Reduced, fn_name: str):
    """(seconds, count) of the programs of jitted function ``fn_name``."""
    s = c = 0
    for name, (secs, count) in red.programs.items():
        if name.endswith(fn_name):
            s += secs
            c += count
    return s, c
