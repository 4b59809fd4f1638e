"""Reduction from the profiler's trace to the benchmark's numbers.

``collect`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps a
compact form: per device, its op events and its program (module) events;
and the benchmark's own host spans (``bench.*``), window span included.
Every other function works on that compact form, which is what the tests
record and replay. Times are in nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)

Interval = Tuple[float, float]


def collect(logdir: str) -> Dict:
    """Compact events of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": [], "lines": [line.name for line in plane.lines]}
            for line in plane.lines:
                kind = ("ops" if line.name in OPS_LINES
                        else "modules" if line.name in MODULE_LINES else None)
                if kind:
                    dev[kind] += [[short_name(e.name), e.start_ns, e.duration_ns]
                                  for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns] for e in line.events
                         if e.name.startswith("bench.")]
    return {"devices": devices, "host": host}


def short_name(name: str) -> str:
    """An op's name without its HLO text (``%fusion.3 = f32[..] ...``)."""
    return name.split(" = ", 1)[0]


def window(tr: Dict) -> Interval:
    spans = [(s, s + d) for n, s, d in tr["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(spans)}")
    return spans[0]


def clip(events: Iterable[Sequence], lo: float, hi: float) -> List[Interval]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    merged: List[list] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def gaps(events, lo: float, hi: float) -> List[Interval]:
    """The idle intervals of the device inside [lo, hi]."""
    out, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def time_by_name(events, pattern: str, lo: float, hi: float) -> float:
    """Summed device time (ns) of events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(b - a for a, b in clip([e for e in events if rx.search(e[0])], lo, hi))


def host_segments(host) -> List[Tuple[float, float, str]]:
    """The host's timeline as disjoint (start, end, activity) segments: in
    each, the innermost benchmark span open (the one opened last). Time in
    no span is left out."""
    spans = [(n, s, d) for n, s, d in host if n != WINDOW_SPAN and d > 0]
    edges = sorted([(s, 1, s + d, n) for n, s, d in spans]
                   + [(s + d, 0, s + d, n) for n, s, d in spans])
    out, open_, t = [], [], None
    for at, opening, end, name in edges:
        if open_ and t is not None and at > t:
            out.append((t, at, open_[-1][1][len("bench."):]))
        if opening:
            open_.append((end, name))
        else:
            open_.remove((end, name))
        t = at
    return out


def idle_by_activity(gap_list: List[Interval], segments) -> Dict[str, float]:
    """Nanoseconds of the device's idle intervals by the host activity they
    overlap; idle time in no benchmark span is "other"."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gap_list:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            overlap = min(e, b) - max(s, a)
            if overlap > 0:
                out[name] += overlap
                covered += overlap
            k += 1
        out["other"] += (b - a) - covered
    return out


def stable_name(name: str) -> str:
    """Program names without the per-compile suffixes XLA appends."""
    return re.sub(r"(\(\d+\)|\.\d+)+$", "", name)


def device_events(tr: Dict, kind: str) -> List[list]:
    return [e for dev in tr["devices"].values() for e in dev[kind]]


def work(dev: Dict) -> List[list]:
    """A device's work: its programs and its ops. The programs' line keeps
    every execution where the ops' line can drop events in a long window,
    and every op runs inside a program, so their union is the busy time."""
    return dev["modules"] + dev["ops"]


def breakdown(tr: Dict, top: int = 10) -> Dict[str, list]:
    """Device programs that took most time, and idle time by host activity."""
    lo, hi = window(tr)
    mods = defaultdict(float)
    for n, a, b in [(e[0], *iv) for e in device_events(tr, "modules")
                    for iv in clip([e], lo, hi)]:
        mods[stable_name(n)] += (b - a) / 1e9
    idle = defaultdict(float)
    n_dev = max(len(tr["devices"]), 1)
    segments = host_segments(tr["host"])
    for dev in tr["devices"].values():
        for name, ns in idle_by_activity(gaps(work(dev), lo, hi), segments).items():
            idle[name] += ns / 1e9 / n_dev
    rank = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:top]
    return {"device_ops": rank(mods), "idle_gaps": rank(idle)}


def device_busy(tr: Dict) -> Tuple[float, float]:
    """(busy seconds averaged over devices, traced window seconds)."""
    lo, hi = window(tr)
    n = max(len(tr["devices"]), 1)
    busy = sum(busy_ns(work(d), lo, hi) for d in tr["devices"].values()) / n
    return busy / 1e9, (hi - lo) / 1e9


def trim(tr: Dict, lo: float, hi: float) -> Dict:
    """The events of ``tr`` that touch [lo, hi] (for recording fixtures)."""
    keep = lambda evs: [e for e in evs if e[1] + e[2] >= lo and e[1] <= hi]
    return {
        "devices": {k: {kind: keep(v[kind]) if kind != "lines" else v[kind] for kind in v}
                    for k, v in tr["devices"].items()},
        "host": keep(tr["host"]),
    }
