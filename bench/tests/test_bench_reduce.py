"""The reduction from trace to metrics, on a recorded trace: 200 ms of the
``testbed.fig4_device`` cell on one TPU v5e (compact form of
``harness.trace.collect``), and the FLOP and byte counts by hand."""

import gzip
import json
import sys
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
    with gzip.open(BENCH / "tests" / "fixtures" / "trace_fig4_device.json.gz", "rt") as f:
        return json.load(f)


def _busy_by_grid(events, lo, hi, step=1000.0):
    """Busy time by sampling the window every ``step`` ns (independent check)."""
    t = np.arange(lo, hi, step) + step / 2
    busy = np.zeros(t.shape, bool)
    for _, s, d in events:
        busy |= (t >= s) & (t < s + d)
    return busy.sum() * step


def test_union_and_idle_share(tr):
    lo, hi = trace.window(tr)
    ops = trace.work(tr["devices"]["/device:TPU:0"])
    busy = trace.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    assert abs(busy - _busy_by_grid(ops, lo, hi)) < 0.01 * busy
    idle = sum(b - a for a, b in trace.gaps(ops, lo, hi))
    assert idle + busy == pytest.approx(hi - lo, rel=1e-9)
    busy_s, window_s = trace.device_busy(tr)
    assert busy_s == pytest.approx(busy / 1e9) and window_s == pytest.approx((hi - lo) / 1e9)


def test_union_merges_overlaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert trace.gaps([["a", 1, 1], ["b", 5, 2]], 0, 10) == [(0, 1), (2, 5), (7, 10)]


def test_program_time_by_name(tr):
    lo, hi = trace.window(tr)
    mods = trace.device_events(tr, "modules")
    fit = [m for m in mods if m[0].startswith("jit_fit_fused(")]
    assert fit
    by_hand = sum(min(s + d, hi) - max(s, lo) for _, s, d in fit)
    assert trace.time_by_name(mods, r"jit_fit_fused\b", lo, hi) == pytest.approx(by_hand)
    assert trace.time_by_name(mods, r"jit__device_round\b", lo, hi) > 0
    assert trace.time_by_name(mods, r"no_such_program", lo, hi) == 0
    assert trace.stable_name("jit_fit_fused(123)") == "jit_fit_fused"


def test_breakdown_names_programs_and_host_activity(tr):
    bd = trace.breakdown(tr)
    names = [n for n, _ in bd["device_ops"]]
    assert "jit_fit_fused" in names and len(names) <= 10
    lo, hi = trace.window(tr)
    idle = sum(v for _, v in bd["idle_gaps"])
    assert idle == pytest.approx(sum(b - a for a, b in trace.gaps(
        trace.work(tr["devices"]["/device:TPU:0"]), lo, hi)) / 1e9)


def test_idle_split_by_innermost_host_span():
    host = [["bench.window", 0, 100], ["bench.a", 10, 50], ["bench.b", 20, 10],
            ["bench.c", 70, 10], ["bench.z", 40, 0]]
    seg = trace.host_segments(host)
    assert seg == [(10, 20, "a"), (20, 30, "b"), (30, 60, "a"), (70, 80, "c")]
    idle = trace.idle_by_activity([(0, 15), (25, 75), (90, 100)], seg)
    assert dict(idle) == {"a": 35, "b": 5, "c": 5, "other": 30}


def _module(path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference(config):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    return cfg, _module(BENCH / "configs" / cfg["reference"])


@pytest.mark.parametrize("config", ["paper_testbed", "cross_device"])
def test_cnn_flops_by_hand(config):
    """The CNN's FLOPs by hand, as each CNN configuration's reference gives
    them per example, and ``round_mfu`` for fixed counters exactly as the
    count before the reference gave it: 2,436,096 x (3 x trained + eval)."""
    assert flops.CONV1_FLOPS == 225_792
    assert flops.CONV2_FLOPS == 1_806_336
    assert flops.FC1_FLOPS == 401_408
    assert flops.FC2_FLOPS == 2_560
    assert flops.CNN_FORWARD_FLOPS == 2_436_096
    cfg, ref = _reference(config)
    assert ref.flops_per_example(cfg) == {"train": 3 * 2_436_096, "eval": 2_436_096}
    ctx = SimpleNamespace(counters={"fit_row_steps": 437.0, "eval_examples": 7600.0},
                          window_s=5.0123456, peak=PEAK, config=cfg, reference=ref)
    trained = 437 * cfg["batch_size"]
    want = 100.0 * (2_436_096 * (3 * trained + 7600)) / (5.0123456 * PEAK["bf16_flops_per_s"])
    assert _module(BENCH / "metrics" / "round_mfu.py").read(ctx) == want


def test_kernel_bytes_and_roofline():
    # one [10, 3, 3, 1, 16] leaf and one [10, 128] leaf
    shapes = [(10, 3, 3, 1, 16), (10, 128)]
    assert flops.fedavg_reduce_bytes(shapes) == (4 * 10 * 144 + 40 + 4 * 144) + (4 * 1280 + 40 + 512)
    pct = flops.roofline_pct(819e6, 0, 0.002, PEAK)  # 1 ms of bytes in 2 ms
    assert pct == pytest.approx(50.0)
    assert flops.roofline_pct(1.0, 0, 0.0, PEAK) is None


def test_metric_readers_on_the_trace(tr):
    cfg, ref = _reference("paper_testbed")
    lo, hi = trace.window(tr)
    busy_s, window_s = trace.device_busy(tr)
    ctx = SimpleNamespace(
        trace=tr, lo=lo, hi=hi, busy_s=busy_s, window_s=window_s, rounds=2,
        counters={"fit_dispatches": 28, "select_s": 0.004, "fit_row_steps": 400,
                  "eval_examples": 800, "compiles": 0, "fedavg_reduce_bytes": 0},
        peak=PEAK, flops=flops, lib=trace, ops=trace.device_events(tr, "ops"),
        modules=trace.device_events(tr, "modules"), config=cfg, reference=ref)

    def read(name):
        return _module(BENCH / "metrics" / f"{name}.py").read(ctx)

    assert read("device.idle_pct") == pytest.approx(100 * (1 - busy_s / window_s))
    fit_ns = trace.time_by_name(ctx.modules, r"jit_(fit_fused|run_chunk|init_state|finalize)\b", lo, hi)
    assert read("fit.device_ms_per_round") == pytest.approx(fit_ns / 1e6 / 2)
    assert read("fit.dispatches_per_round") == 14
    assert read("host.select_ms_per_round") == pytest.approx(2.0)
    assert read("round_mfu") == pytest.approx(
        100 * 2_436_096 * (3 * 400 * 32 + 800) / (window_s * PEAK["bf16_flops_per_s"]))
    assert read("fedavg_reduce_roofline") is None  # no bytes, no share
    assert read("jit.compiles_in_window") == 0
