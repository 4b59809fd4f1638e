"""The program's own spans (``harness.program`` and the readers of
``fl.*`` spans): on the recorded trace of a program without spans, on a
recorded ``testbed.fig4_device`` round with spans, and by hand."""

import gzip
import json
import sys
import tempfile
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, __import__("os").path.dirname(__file__))
from helpers import BENCH  # noqa: E402

sys.path.insert(0, str(BENCH))
from harness import flops, trace  # noqa: E402

PEAK = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


@pytest.fixture(scope="module")
def tr():
    """200 ms of a traced ``testbed.fig4_device`` run of a program without spans."""
    with gzip.open(BENCH / "tests" / "fixtures" / "trace_fig4_device.json.gz", "rt") as f:
        return json.load(f)


PROGRAM_READERS = ("fit.prep_idle_ms_per_round", "fit.h2d_mb_per_round", "post.idle_ms_per_round",
                   "host.syncs_per_round", "host.sync_ms_per_round",
                   "host.shard_build_ms_per_round")
PREP = ("fl.fit.batches", "fl.fit.h2d", "fl.fit.anchors")
POST = ("fl.gather_rows", "fl.divergence", "fl.aggregate", "fl.finish_round", "fl.evaluate")


@pytest.fixture(scope="module")
def tr_program():
    """One whole engine round of a traced ``testbed.fig4_device`` run on one
    TPU v5e, program spans included (``bench/program_trace.py --fixture``)."""
    with gzip.open(BENCH / "tests" / "fixtures" / "trace_fig4_device_program.json.gz", "rt") as f:
        return json.load(f)


def _ctx(tr, rounds):
    lo, hi = trace.window(tr)
    busy_s, window_s = trace.device_busy(tr)
    return SimpleNamespace(
        trace=tr, lo=lo, hi=hi, busy_s=busy_s, window_s=window_s, rounds=rounds,
        counters={}, peak=PEAK, flops=flops, lib=trace, ops=trace.device_events(tr, "ops"),
        modules=trace.device_events(tr, "modules"), config={"batch_size": 32})


def _read(name, ctx):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _idle_in_by_grid(tr, names, lo, hi, step=1000.0):
    """Device idle inside the named program spans, by sampling the window
    every ``step`` ns (independent check; masks built from difference
    arrays, so a long trace samples quickly)."""
    n = int((hi - lo) // step)

    def mask(intervals):
        diff = np.zeros(n + 1, int)
        for s, d in intervals:  # samples t = lo + (i + 1/2) step inside [s, s + d)
            a = int(np.clip(np.ceil((s - lo) / step - 0.5), 0, n))
            b = int(np.clip(np.ceil((s + d - lo) / step - 0.5), 0, n))
            diff[a] += 1
            diff[b] -= 1
        return np.cumsum(diff)[:n] > 0

    busy = mask([(s, d) for _, s, d in trace.work(tr["devices"]["/device:TPU:0"])])
    inside = mask([(s, d) for name, s, d, _ in tr["program"] if name in names])
    return (~busy & inside).sum() * step


def test_program_readers_read_nothing_without_program_spans(tr, monkeypatch, tmp_path):
    """The recorded trace of a program without spans: every new reader
    reads nothing, and finds no traced run's profile to read from."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    ctx = _ctx(dict(tr), rounds=2)
    for name in PROGRAM_READERS:
        assert _read(name, ctx) is None, name
    assert ctx.trace["program"] == []


def test_program_readers_on_the_trace(tr_program):
    from harness import program

    lo, hi = trace.window(tr_program)
    ctx = _ctx(tr_program, rounds=1)
    events = tr_program["program"]
    assert _read("fit.prep_idle_ms_per_round", ctx) == pytest.approx(
        _idle_in_by_grid(tr_program, PREP, lo, hi) / 1e6, rel=0.01)
    assert _read("post.idle_ms_per_round", ctx) == pytest.approx(
        _idle_in_by_grid(tr_program, POST, lo, hi) / 1e6, rel=0.01)
    copies = [e for e in events if e[0] == "fl.fit.h2d"]
    # 8 rows x 4 steps x 32 examples of a 28 x 28 f32 image and an i32 label
    assert {e[3]["bytes"] for e in copies} == {8 * 4 * 32 * (784 * 4 + 4)}
    assert _read("fit.h2d_mb_per_round", ctx) == pytest.approx(len(copies) * 3.215360)
    syncs = [e for e in events if e[0].startswith("fl.sync.")]
    assert _read("host.syncs_per_round", ctx) == len(syncs) > 0
    by_hand = sum(min(s + d, hi) - max(s, lo) for _, s, d, _ in syncs)  # syncs never overlap
    assert _read("host.sync_ms_per_round", ctx) == pytest.approx(by_hand / 1e6)
    assert _read("host.shard_build_ms_per_round", ctx) is None  # a grid builds no shards
    # the benchmark's own readers read this trace as they read the other
    assert _read("fit.device_ms_per_round", ctx) > 0
    assert _read("device.idle_pct", ctx) == pytest.approx(
        100 * (1 - ctx.busy_s / ctx.window_s))
    assert program.count(program.select(events, "fl.round"), lo, hi) == 1


def test_idle_by_innermost_program_span(tr_program):
    from harness import program

    lo, hi = trace.window(tr_program)
    idle = program.idle_by_span(tr_program, tr_program["program"], lo, hi)
    gaps = trace.gaps(trace.work(tr_program["devices"]["/device:TPU:0"]), lo, hi)
    assert sum(idle.values()) == pytest.approx(sum(b - a for a, b in gaps) / 1e9)
    assert "fl.fit.batches" in idle and "fl.gather_rows" in idle


def test_program_helpers_by_hand():
    from harness import program

    events = [["fl.round", 0, 100, {"round": 3}], ["fl.fit.h2d", 10, 20, {"bytes": 7}],
              ["fl.fit.h2d", 40, 10, {"bytes": 5}], ["fl.sync.eval", 60, 10, {"bytes": 8}],
              ["fl.sync.transport", 65, 10, {}], ["fl.fit.h2d", 120, 5, {"bytes": 100}]]
    assert [e[0] for e in program.select(events, "fl.sync.")] == ["fl.sync.eval",
                                                                 "fl.sync.transport"]
    assert len(program.select(events, "fl.fit.h2d", "fl.round")) == 4
    assert program.count(program.select(events, "fl.fit.h2d"), 0, 100) == 2
    assert program.stat_sum(events, "bytes", 0, 100) == 20
    assert program.span_ns(program.select(events, "fl.sync."), 0, 100) == 15
    tr = {"devices": {"d": {"modules": [["m", 15, 30]], "ops": []}}}
    # idle in [0, 100): [0, 15) and [45, 100); h2d open over [10, 30) and [40, 50)
    assert program.idle_in(tr, program.select(events, "fl.fit.h2d"), 0, 100) == 5 + 5
    # innermost: round [0, 10), h2d [10, 30), round [30, 40), h2d [40, 50), round
    # [50, 60), eval [60, 65), transport [65, 75) (opened last), round [75, 100)
    assert program.idle_by_span(tr, events[:5], 0, 100) == pytest.approx({
        "fl.round": (10 + 10 + 25) * 1e-9, "fl.fit.h2d": 10e-9,
        "fl.sync.eval": 5e-9, "fl.sync.transport": 10e-9, "other": 0.0})
    small = program.trim({"devices": tr["devices"], "host": [], "program": events}, 30, 70)
    assert [e[0] for e in small["program"]] == [e[0] for e in events[:5]]
    assert trace.window(small) == (30, 70)


def test_program_spans_found_and_collected(monkeypatch, tmp_path):
    """A profile with program spans, written where a traced run of
    ``bench/run.py`` writes it: found by its window, read with its stats."""
    import jax

    from harness import program
    from repro.utils.spans import span

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with span("fit.h2d", bytes=4096) as s:
            s.set_metadata(rows=8)
    jax.profiler.stop_trace()
    tr = trace.collect(logdir)
    lo, hi = trace.window(tr)
    ctx = SimpleNamespace(trace=tr, lo=lo, hi=hi)
    (got,) = program.attach(ctx)
    assert got[0] == "fl.fit.h2d" and got[3] == {"bytes": 4096, "rows": 8}
    assert lo <= got[1] and got[1] + got[2] <= hi and tr["program"] == [got]
    other = SimpleNamespace(trace={}, lo=lo + 1, hi=hi)
    assert program.attach(other) is None  # no profile has that window
