#!/usr/bin/env python3
"""Chip benchmark of the FL round engine: one cell per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration file (``bench/configs/<config>.json``, which names its plain
references beside it) and a traffic file (``bench/traffic/<traffic>.json``);
its limits and the faults it can have are in ``bench/limits/<cell>.json``
and each per-layer metric is read by ``bench/metrics/<metric>.py``. The
run:

1. stamps the device and exits 2 unless JAX's first device is a TPU and
   there are as many chips as the cell asks for;
2. keeps JAX's persistent compilation cache in the checkout;
3. builds the cell's inputs and weights from the seed, and runs the
   engine's first rounds (a whole sweep, or the population's first
   committed rounds) as set-up, compiling the shapes the cell uses;
4. measures for ``--seconds``: whole sweeps back to back, or population
   rounds back to back; with ``--trace 1`` under the profiler, for at most
   ``TRACE_SECONDS``;
5. follows a sample of points, drawn from the seed, with the plain FL
   reference, holds every round's transport outcomes against the flow
   reference, and prints each number compared beside its limit;
6. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` when traced), then the
   numbers compared, each beside its limit, under ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# the TPU runtime's logs go under this run's TMPDIR, not a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))

import numpy as np  # noqa: E402

TRACE_SECONDS = 5.0  # a traced window: long enough for tens of rounds
CHECK_ROUNDS = 3  # rounds the FL reference follows from each start


def load_manifest(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(name: str, known) -> str:
    """The entry of ``known`` that serves the metric ``name``: the name
    itself, else its longest dotted prefix. A metric split by the cells
    that report it (``<metric>.<group>``) shares ``<metric>``'s reading."""
    parts = name.split(".")
    for i in range(len(parts), 0, -1):
        if ".".join(parts[:i]) in known:
            return ".".join(parts[:i])
    raise KeyError(f"nothing reads metric {name!r}")


def resolve(manifest: Dict, workload: str, bench: Path = BENCH) -> Dict:
    """A cell with its configuration, traffic, limits and metric readers,
    each found by name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in manifest["configs"] if c["name"] == w["config"])
    per_layer = [m for m in manifest["per_layer"] if workload in m.get("workloads", [workload])]
    readers = {p.stem for p in (bench / "metrics").glob("*.py")}
    return {
        "name": workload,
        "chips": w["chips"],
        "config": json.loads((ROOT / conf["file"]).read_text()),
        "config_dir": (ROOT / conf["file"]).parent,
        "traffic": json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        **json.loads((bench / "limits" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in manifest["end_to_end"] if workload in m.get("workloads", [workload])],
        "per_layer": per_layer,
        "readers": {m["name"]: bench / "metrics" / f"{by_name(m['name'], readers)}.py"
                    for m in per_layer},
        "peaks": json.loads((bench / "peaks.json").read_text()),
    }


def _draws(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), 7, salt]))


class Window:
    """The measured window: its clock, and the profiler when traced."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        self._ann = None

    def open(self, probe, seconds: float):
        import jax

        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        probe.counting = True
        self.dispatches0 = probe.fit_dispatches()
        self.t0 = time.perf_counter()
        probe.deadline = self.t0 + seconds

    def close(self, probe):
        import jax

        self.t1 = time.perf_counter()
        probe.counting = False
        probe.counters["fit_dispatches"] = probe.fit_dispatches() - self.dispatches0
        if self.trace:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        kept = probe.kept()
        print(f"bench: the check kept at most {kept['device']} B of whole trees on the device "
              f"and {kept['host']} B on the host (a tree is {kept['tree']} B)", file=sys.stderr)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def run_grid(traffic, task, probe, seconds: float, win: Window, seed: int):
    """Set-up: sweep 0. Window: sweeps 1, 2, ... until ``seconds`` pass."""
    from harness import check as chk

    rng = _draws(seed, 1)
    sampled = {0} | set(int(k) for k in rng.choice(np.arange(1, 6), size=2, replace=False))
    kept = {}  # sweep -> (points, histories), sampled sweeps only
    groups = {}  # (sweep, id(ServerConfig)) -> the point's (link, tcp)
    out = SimpleNamespace(client_rounds=0, failed=0, runs=[], error=None)

    def sweep(k):
        probe.run_id = k
        with probe.span("build_points"):
            pts = traffic.points(k)
        groups.update({(k, id(p.config)): spec for p, spec in zip(pts, traffic.specs)})
        if k in sampled:
            probe.capture_keys |= {id(p.config) for p in pts}
        res = traffic.run(task, pts)
        probe.end_run()
        if k in sampled:
            kept[k] = (pts, res.histories)
        return res

    sweep(0)
    out.setup_s = time.perf_counter() - T_START
    win.open(probe, seconds)
    k = 1
    while not probe.closed:
        try:
            res = sweep(k)
        except Exception:  # a lost sweep: its work is failed, and the run is not correct
            out.error = traceback.format_exc()
            n = traffic.n_points * traffic.cfg["n_clients"] * traffic.cfg["rounds"]
            out.client_rounds += n
            out.failed += n
            break
        out.runs.append(k)
        for h in res.histories:
            n = sum(r.selected for r in h.rounds)
            out.client_rounds += n
            out.failed += n if h.status == "diverged" else 0
        del res
        k += 1
    win.close(probe)

    out.flow_groups = []
    for key, recs in probe.flows.items():
        for run in sorted({r["run"] for r in recs}):
            if (run, key) in groups:
                out.flow_groups.append((traffic.flow(*groups[(run, key)]),
                                        [r for r in recs if r["run"] == run]))
    cands = []
    for k in sorted(kept):
        pts, hists = kept[k]
        for i, (p, h) in enumerate(zip(pts, hists)):
            if len(h.rounds) >= CHECK_ROUNDS and all(
                    not r.failed_round for r in h.rounds[:CHECK_ROUNDS]):
                cands.append((k, traffic.seeds(k)[i], id(p.config), h))
    n = min(traffic.traffic["check_points"], len(cands))
    out.samples = []
    for j in (rng.choice(len(cands), size=n, replace=False) if n else []):
        k, s, key, h = cands[int(j)]
        point = dict(traffic.reference_point(s), delivered={
            r["rnd"]: r["committed"] for r in probe.flows[key] if r["run"] == k})
        rounds = list(range(CHECK_ROUNDS))
        out.samples.append((point, rounds, None,
                            chk.program_point(h, probe.captured[key], rounds, probe.n_leaves)))
    return out


def run_population(traffic, task, probe, seconds: float, win: Window, seed: int):
    """One server: set-up runs rounds until ``warm_rounds`` have committed,
    the window continues the same ``run()`` until ``seconds`` pass. The FL
    reference follows rounds 0-2 and three consecutive window rounds drawn
    from the seed, from the program's params before them."""
    from harness import check as chk
    from harness.probe import TreeBuffer

    server = traffic.server(task)
    key = id(server.config)
    probe.capture_keys.add(key)
    buf = probe.buffers[key] = TreeBuffer(CHECK_ROUNDS + 1)
    # drawn from the seed alone, within the rounds a full window holds,
    # so that a seed follows the same rounds in every run
    offset = int(_draws(seed, 2).integers(0, traffic.traffic["window_sample_rounds"]))
    out = SimpleNamespace(client_rounds=0, failed=0, runs=["window"], error=None)
    state = {"phase": "setup", "first": None}
    warm = traffic.traffic["warm_rounds"]

    def hook(srv, rnd):
        if state["phase"] == "setup" and srv.history.completed_rounds >= warm:
            out.setup_s = time.perf_counter() - T_START
            state.update(phase="window", first=rnd)
            buf.until = rnd + offset + CHECK_ROUNDS - 1
            probe.run_id = "window"
            win.open(probe, seconds)

    probe.on_begin_round = hook
    probe.run_id = "setup"
    try:
        server.run()
    except Exception:
        out.error = traceback.format_exc()
    probe.end_run()
    if state["phase"] == "setup":
        out.setup_s = time.perf_counter() - T_START
        win.open(probe, seconds)
    win.close(probe)
    h = server.history
    first = state["first"]
    window_rounds = [r for r in h.rounds if first is not None and r.round_idx >= first]
    out.client_rounds = sum(r.selected for r in window_rounds)
    out.failed = out.client_rounds if (h.status == "diverged" or out.error) else 0
    out.flow_groups = [(traffic.flow(traffic.link, traffic.tcp), probe.flows[key])]

    point = dict(traffic.reference_point(),
                 delivered={r["rnd"]: r["committed"] for r in probe.flows[key]})
    rounds = list(range(CHECK_ROUNDS))
    out.samples = [(point, rounds, None,
                    chk.program_point(h, probe.captured[key], rounds, probe.n_leaves))]
    last = max((r.round_idx for r in window_rounds), default=-1)
    del server
    if first is not None and last - CHECK_ROUNDS + 1 >= first:
        r0 = min(first + offset, last - CHECK_ROUNDS + 1)
        rounds = list(range(r0, r0 + CHECK_ROUNDS))
        # the tree after the last round captured before r0 (set-up committed
        # rounds, and the buffer holds a round before any 3 that follow it)
        base = buf.trees[max(r for r in buf.trees if r < r0)]
        norms = {r: chk.leaf_norms(buf.trees[r], base) for r in rounds if r in buf.trees}
        out.samples.append((point, rounds, base,
                            chk.program_point(h, norms, rounds, probe.n_leaves)))
    else:  # the window held too few rounds to follow: nothing to compare
        out.samples.append((point, [], None, None))
    buf.trees.clear()
    return out


def check(cell: Dict, run, ref_mod, *, control: bool = False,
          detail: Optional[List] = None) -> Dict:
    """Follow the run's sampled points with the FL reference, beside the
    run's transport numbers. With ``control``, the FL reference computed
    one precision below stands in for the program. ``detail`` receives
    each sample's rounds, losses and readings."""
    import jax.numpy as jnp

    from harness import check as chk

    cfg = cell["config"]
    low = {"float32": jnp.bfloat16}[cfg["dtype"]]
    hp = dict(lr=cfg["lr"], momentum=cfg["momentum"], clip=cfg["clip_norm"])
    readings = []
    for point, rounds, start, prog in run.samples:
        if prog is None:
            readings.append({k: float("inf") for k in chk.NAMES})
            continue
        base = ref_mod.init_params(point["seed"]) if start is None else start
        ref = chk.replay_norms(ref_mod.replay(point, rounds, start=start, **hp), base)
        if control:
            lo = chk.replay_norms(ref_mod.replay(point, rounds, start=start, dtype=low, **hp), base)
            prog = dict(prog, losses=[x["loss"] for x in lo], norms=[x["norms"] for x in lo])
        readings.append(chk.compare(prog, ref, prog["committed"]))
        if detail is not None:
            detail.append({"rounds": rounds, "losses": prog["losses"],
                           "ref_losses": [x["loss"] for x in ref], **readings[-1]})
    values = chk.worst(readings) if readings else {}
    values.update(run.transport)
    return chk.verdict(values, cell["limits"])


def transport(cell: Dict, run, flow_mod, seed: int, quorum: int, goal) -> Dict[str, float]:
    """The transport numbers over every group of flows the run received."""
    from harness import check as chk

    samples = cell["traffic"]["flow_samples"]
    stochastic = cell["traffic"]["server"].get("stochastic", False)
    cache = {}
    worst = {"delivery_z": 0.0, "commit_errors": 0.0}
    if stochastic:
        worst["arrival_z"] = 0.0
    for flow, recs in run.flow_groups:
        if not recs:
            continue

        def ref(connected, flow=flow):
            k = (json.dumps(flow, sort_keys=True), connected)
            if k not in cache:
                cache[k] = flow_mod.completion(
                    flow["link"], flow["tcp"], down_bytes=flow["down_bytes"],
                    up_bytes=flow["up_bytes"], idle_s=flow["idle_s"], deadline=flow["deadline"],
                    connected=connected, n=samples, seed=seed)
            return cache[k]

        for name, v in chk.delivery(recs, ref, samples, flow["deadline"], stochastic).items():
            worst[name] = max(worst[name], v)
        worst["commit_errors"] += chk.commit_errors(recs, quorum, goal, flow["deadline"])
    return worst


def per_layer(cell: Dict, probe, win: Window, device_kind: str, rounds: int, reference):
    """Every per-layer metric of the cell that its reader finds, from the
    traced window; ``reference`` is the configuration's reference module."""
    from harness import flops, trace

    tr = trace.collect(win.logdir)
    lo, hi = trace.window(tr)
    busy_s, window_s = trace.device_busy(tr)
    if device_kind not in cell["peaks"]["devices"]:
        raise ValueError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    ctx = SimpleNamespace(
        trace=tr, lo=lo, hi=hi, busy_s=busy_s, window_s=window_s, rounds=rounds,
        counters=dict(probe.counters), peak=cell["peaks"]["devices"][device_kind],
        flops=flops, lib=trace, ops=trace.device_events(tr, "ops"),
        modules=trace.device_events(tr, "modules"), config=cell["config"], reference=reference,
    )
    metrics = {}
    for m in cell["per_layer"]:
        value = _module(cell["readers"][m["name"]]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, trace.breakdown(tr), busy_s, window_s, tr


def run_cell(cell: Dict, seed: int, seconds: float, trace_on: bool, *,
             control: bool = False):
    """One run of a cell. Returns the result line as a dict, and what else
    the run saw: its round times, its error if any, the compact trace of a
    traced run, and (with ``control``) the control's checks."""
    import jax

    from harness import probe as probe_mod
    from harness import traffic as traffic_mod

    cfg = cell["config"]
    ref_mod = _module(cell["config_dir"] / cfg["reference"])
    flow_mod = _module(cell["config_dir"] / cfg["flow_reference"])
    traffic = traffic_mod.build(cfg, cell["traffic"], seed)
    task = traffic_mod.make_task(cfg, ref_mod.init_from_key)
    grid = traffic.engine == "grid"
    probe = probe_mod.Probe(task, trace=trace_on, capture_rounds=set(range(CHECK_ROUNDS)))
    probe.install()
    win = Window(trace_on)
    window_s = min(seconds, TRACE_SECONDS) if trace_on else seconds
    precision = (contextlib.nullcontext() if cfg["matmul_precision"] == "default"
                 else jax.default_matmul_precision(cfg["matmul_precision"]))
    try:
        with precision:
            run = (run_grid if grid else run_population)(traffic, task, probe, window_s, win, seed)
    finally:
        probe.uninstall()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    times = probe.round_times(run.runs)
    result = {"correct": False, "attempted": int(run.client_rounds), "failed": int(run.failed)}
    info = SimpleNamespace(round_times=times, error=run.error, trace=None, control=None)
    extra = {}
    if trace_on:
        metrics, bd, busy_s, traced_s, info.trace = per_layer(
            cell, probe, win, dev.device_kind, len(times), ref_mod)
        device.update(busy_s=busy_s, window_s=traced_s)
        extra = {"breakdown": bd}
        shutil.rmtree(win.logdir, ignore_errors=True)
    else:
        values = {
            "client_rounds_per_s": run.client_rounds / win.seconds,
            "round_p90_ms": float(np.percentile(times, 90)) * 1e3 if times else float("nan"),
            "setup_s": run.setup_s,
        }
        metrics = {m["name"]: {"value": values[by_name(m["name"], values)], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    run.transport = transport(cell, run, flow_mod, seed, traffic.quorum,
                              None if grid else cfg["goal"])
    del traffic, task, probe
    info.detail, info.control_detail = [], []
    checks = check(cell, run, ref_mod, detail=info.detail)
    if control:
        info.control = check(cell, run, ref_mod, control=True, detail=info.control_detail)
    from harness import check as chk

    result.update(correct=bool(chk.passes(checks)) and run.error is None and run.client_rounds > 0,
                  metrics=metrics, device=device, **extra, checks=checks)
    return result, info


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(load_manifest(), args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result, info = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    from harness import check as chk

    if info.error:
        print(info.error, file=sys.stderr)
    print(f"bench: {args.workload} seed {args.seed}: {len(info.round_times)} rounds in the window",
          file=sys.stderr)
    if info.trace is not None:
        from harness import trace

        lo, hi = trace.window(info.trace)
        for kind in ("modules", "ops"):
            busy = trace.busy_ns(trace.device_events(info.trace, kind), lo, hi) / 1e9
            print(f"bench: device busy by {kind} line: {busy!r} s", file=sys.stderr)
    for line in chk.describe(result["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
