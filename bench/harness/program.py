"""The program's own spans in a traced run, beside ``harness.trace``.

The program names its phases with ``fl.*`` spans on the profiler's clock
(``repro.utils.spans``), counts riding on them as stats. ``attach`` reads
those host events, ``[name, start_ns, duration_ns, {stat: value}]``, from
a traced run's profile into its compact trace under ``program``; the
functions below read them against the device's idle time. A program that
records no ``fl.*`` span reads as nothing (``attach`` returns None).
"""

from __future__ import annotations

import glob
import os
import tempfile
from typing import Dict, List, Optional, Sequence

from harness import trace

PREFIX = "fl."
TRACE_DIRS = "bench_trace_*"  # where ``run.Window`` puts a traced run's profile


def _host_events(path: str, prefixes: Sequence[str]) -> List[list]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [[e.name, e.start_ns, e.duration_ns, dict(e.stats)]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(tuple(prefixes))]


def _xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    return paths[-1] if paths else None


def _find(lo: float) -> Optional[List[list]]:
    """The program events of the traced run whose window starts at ``lo``:
    the newest profile under the temporary directory whose window span
    starts there."""
    dirs = sorted(glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS)),
                  key=os.path.getmtime, reverse=True)
    for d in dirs:
        path = _xplane(d)
        if path is None:
            continue
        events = _host_events(path, (PREFIX, trace.WINDOW_SPAN))
        if any(e[0] == trace.WINDOW_SPAN and e[1] == lo for e in events):
            return [e for e in events if e[0].startswith(PREFIX)]
    return None


def attach(ctx) -> Optional[List[list]]:
    """The run's program events, read once into ``ctx.trace["program"]``;
    None when the run recorded none."""
    if "program" not in ctx.trace:
        ctx.trace["program"] = _find(ctx.lo) or []
    return ctx.trace["program"] or None


def select(events: List[list], *names: str) -> List[list]:
    """Events named one of ``names``; a name ending in ``.`` is a prefix."""
    exact = {n for n in names if not n.endswith(".")}
    prefixes = tuple(n for n in names if n.endswith("."))
    return [e for e in events if e[0] in exact or (prefixes and e[0].startswith(prefixes))]


def _within(events: List[list], lo: float, hi: float) -> List[list]:
    return [e for e in events if lo <= e[1] < hi]


def count(events: List[list], lo: float, hi: float) -> int:
    """Events that start inside [lo, hi)."""
    return len(_within(events, lo, hi))


def stat_sum(events: List[list], stat: str, lo: float, hi: float) -> float:
    """The summed ``stat`` of the events that start inside [lo, hi)."""
    return float(sum(e[3].get(stat, 0) for e in _within(events, lo, hi)))


def span_ns(events: List[list], lo: float, hi: float) -> float:
    """Time inside [lo, hi) that any of the events covers."""
    return trace.busy_ns([e[:3] for e in events], lo, hi)


def _overlap_ns(xs: List[tuple], ys: List[tuple]) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        (a, b), (c, d) = xs[i], ys[j]
        total += max(0.0, min(b, d) - max(a, c))
        if b <= d:
            i += 1
        else:
            j += 1
    return total


def idle_in(tr: Dict, events: List[list], lo: float, hi: float) -> float:
    """Device idle time (ns, averaged over devices) inside [lo, hi) while
    any of the events is open."""
    spans = trace.union(trace.clip([e[:3] for e in events], lo, hi))
    total = sum(_overlap_ns(trace.gaps(trace.work(dev), lo, hi), spans)
                for dev in tr["devices"].values())
    return total / max(len(tr["devices"]), 1)


def idle_by_span(tr: Dict, events: List[list], lo: float, hi: float) -> Dict[str, float]:
    """Device idle time (s, averaged over devices) by the innermost program
    span open on the host; idle time in none is "other"."""
    # host_segments reads the benchmark's own ``bench.`` names
    segments = trace.host_segments([["bench." + n, s, d] for n, s, d, _ in events])
    out: Dict[str, float] = {}
    for dev in tr["devices"].values():
        for name, ns in trace.idle_by_activity(trace.gaps(trace.work(dev), lo, hi),
                                               segments).items():
            out[name] = out.get(name, 0.0) + ns / 1e9 / max(len(tr["devices"]), 1)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def trim(tr: Dict, lo: float, hi: float) -> Dict:
    """``trace.trim`` that keeps the program events too, with the window
    span set to [lo, hi] (for recording fixtures)."""
    out = trace.trim(tr, lo, hi)
    out["host"] = [h for h in out["host"] if h[0] != trace.WINDOW_SPAN]
    out["host"].append([trace.WINDOW_SPAN, lo, hi - lo])
    out["program"] = [e for e in tr.get("program", []) if e[1] + e[2] >= lo and e[1] <= hi]
    return out
