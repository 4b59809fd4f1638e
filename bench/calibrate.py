#!/usr/bin/env python3
"""Readings for a cell's limits, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 3 \
        [--faults state_unchanged,half_batch --fault-seeds 21,22,23]

For each seed it runs the cell as ``bench/run.py`` does, with a short
window, and prints one JSON line: the program's readings against the
references, and the control's (the FL reference computed in bfloat16 in
the program's place) on the same points; then, for each fault of
``--faults`` and each of ``--fault-seeds``, the readings of the program
with that fault planted. The limits in ``bench/limits/<cell>.json`` are
set between the program's largest reading and the least of the
control's and the faults'. ``--trace-out`` writes the compact trace of one
traced run (its first second) for inspection.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace-out", default=None)
    ap.add_argument("--faults", default="", help="faults of harness.faults to plant, by name")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    cell = bench.resolve(bench.load_manifest(), args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness import faults

    runs = [(None, int(s)) for s in args.seeds.split(",") if s]
    runs += [(f, int(s)) for f in args.faults.split(",") if f
             for s in args.fault_seeds.split(",") if s]
    for fault, seed in runs:
        t = time.perf_counter()
        with (faults.planted(fault) if fault else contextlib.nullcontext()):
            res, info = bench.run_cell(cell, seed, args.seconds, False, control=not fault)
        line = {
            "seed": seed, "correct": res["correct"],
            "program": {k: v["value"] for k, v in res["checks"].items()},
            "fault": fault,
            "control": {k: v["value"] for k, v in (info.control or {}).items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "samples": info.detail, "control_samples": info.control_detail,
            "rounds": len(info.round_times), "attempted": res["attempted"],
            "error": info.error, "wall_s": time.perf_counter() - t,
        }
        print(json.dumps(line), flush=True)
    if args.trace_out:
        from harness import trace

        res, info = bench.run_cell(cell, int(args.seeds.split(",")[0]), args.seconds, True)
        lo, _ = trace.window(info.trace)
        small = trace.trim(info.trace, lo, lo + 1e9)
        small["host"] = [h for h in small["host"] if h[0] != trace.WINDOW_SPAN]
        small["host"].append([trace.WINDOW_SPAN, lo, 1e9])
        with open(args.trace_out, "w") as f:
            json.dump(small, f)
        print(json.dumps({"traced": res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
