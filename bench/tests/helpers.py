"""Loads the benchmark's entry module and manifest for the tests (the
entry module puts ``bench/`` and the program's ``src/`` on the path)."""

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SEED = 2**31 + 4242
# a test run's window: the population cell's reference follows three
# window rounds, which take a few seconds on the CPU
SECONDS = {"grid": 1.0, "population": 6.0}


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_cell(name):
    """The cell with its ladder cut to its first and last values (grid) or
    a goal of 20 (population), and fewer simulated flows in the flow
    reference, so that a whole run fits a test's time on the CPU."""
    run = load_run()
    cell = run.resolve(run.load_manifest(), name)
    cell["traffic"]["flow_samples"] = 400
    if cell["traffic"]["engine"] == "grid":
        cell["traffic"]["values"] = cell["traffic"]["values"][::len(cell["traffic"]["values"]) - 1]
        cell["traffic"]["check_points"] = 2
        cell["config"]["rounds"] = 3
    else:
        cell["config"]["goal"] = 20
    return run, cell


def small_run(name, **kw):
    """One whole run of the small cell (``kw`` for ``run_cell``)."""
    run, cell = small_cell(name)
    return run.run_cell(cell, SEED, SECONDS[cell["traffic"]["engine"]], False, **kw)
