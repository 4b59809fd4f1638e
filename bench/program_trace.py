#!/usr/bin/env python3
"""The program's own spans in a cell's traced runs, on the chip.

    python3 bench/program_trace.py --workload <cell> --seeds 11,12 \
        [--guard] [--fixture <path.json.gz>]

For each seed it makes one traced run of the cell as ``bench/run.py
--trace 1`` does and prints one JSON line: the run's metrics and
breakdown, the rounds the window counted beside its ``fl.round`` spans,
``fl.select`` time per round beside the benchmark's own
``host.select_ms_per_round``, and the device's idle time by the innermost
program span open on the host (``harness.program.idle_by_span``; "other":
none). ``--fixture`` writes the compact trace, program spans included, of
the first run's second whole round in the window.

``--guard`` first runs one whole grid sweep, or one population round,
under ``jax.transfer_guard_device_to_host("disallow")``, after the same
work unguarded has compiled every program: any device-to-host read on the
round path that does not go through ``repro.utils.spans.to_host`` raises.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
import traceback

import run as bench


def guard(cell, seed: int) -> dict:
    import jax

    from harness import traffic as traffic_mod

    cfg = cell["config"]
    ref_mod = bench._module(cell["config_dir"] / cfg["reference"])
    traffic = traffic_mod.build(cfg, cell["traffic"], seed)
    task = traffic_mod.make_task(cfg, ref_mod.init_from_key)
    if traffic.engine == "grid":
        work = [lambda k=k: traffic.run(task, traffic.points(k)) for k in (0, 1)]
    else:
        work = [lambda: traffic.server(task).run(stop_after_round=1)] * 2
    work[0]()
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            work[1]()
    except Exception:  # the guard's error names the read
        return {"guard": "raised", "error": traceback.format_exc()}
    return {"guard": "ok"}


def traced(cell, seed: int, fixture=None) -> dict:
    from harness import program, trace

    res, info = bench.run_cell(cell, seed, bench.TRACE_SECONDS, True)
    tr = info.trace
    lo, hi = trace.window(tr)
    events = tr.get("program") or []
    # the window's rounds: those that selected a cohort inside it (the round
    # that finds the window closed selects nothing)
    selects = [e for e in program.select(events, "fl.select") if lo <= e[1] < hi]
    rounds = [r for r in program.select(events, "fl.round")
              if any(r[1] <= e[1] < r[1] + r[2] for e in selects)]
    n = len(info.round_times)
    idle = program.idle_by_span(tr, events, lo, hi)
    line = {
        "seed": seed, "correct": res["correct"], "device": res["device"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "window_rounds": n, "fl_round_spans": len(rounds),
        "fl_select_ms_per_round": program.span_ns(selects, lo, hi) / 1e6 / n if n else None,
        "idle_by_span_s": idle,
        "idle_round_or_none_share": ((idle.get("fl.round", 0.0) + idle.get("other", 0.0))
                                     / max(sum(idle.values()), 1e-12)),
        "breakdown": res["breakdown"],
    }
    if fixture and len(rounds) >= 2:
        _, s, d, _ = rounds[1]
        with gzip.open(fixture, "wt") as f:
            json.dump(program.trim(tr, s, s + d), f)
        line["fixture"] = {"path": fixture, "round": rounds[1][3].get("round"), "ns": d}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--guard", action="store_true")
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    cell = bench.resolve(bench.load_manifest(), args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("program_trace: no TPU", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.guard:
        print(json.dumps({"workload": args.workload, **guard(cell, seeds[0])}), flush=True)
    for i, seed in enumerate(seeds):
        line = traced(cell, seed, args.fixture if i == 0 else None)
        print(json.dumps({"workload": args.workload, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
