"""``BENCHMARK.json`` against the benchmark's contract: every name resolves
to its file, names and units use the allowed characters, every per-layer
metric's cells report the metric it moves, and each traffic builds its
points at the stated sizes without running them."""

import json
import re
import sys

import pytest

sys.path.insert(0, __import__("os").path.dirname(__file__))
from helpers import BENCH, CELLS, MANIFEST, load_run  # noqa: E402

ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert MANIFEST["paths"] == ["bench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MANIFEST[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append(e["name"])
            for k in ("why", "layer", "source"):
                if k in e and group in ("configs", "workloads", "per_layer"):
                    assert LINE.match(e[k]), (e["name"], k)
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in MANIFEST["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])


def test_bounds():
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in MANIFEST["end_to_end"]} >= {"setup_s", "client_rounds_per_s"}


FL_NUMBERS = {"cohort_mismatches", "update_gap", "change_gap"}
LOSS_NUMBERS = ({"loss_gap"}, {"loss_gap_nats"})  # a cell compares one of the two
TRANSPORT_NUMBERS = {"delivery_z", "commit_errors"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    run = load_run()
    c = run.resolve(run.load_manifest(), cell)
    for ref in ("reference", "flow_reference"):
        assert (c["config_dir"] / c["config"][ref]).is_file()
    for name, path in c["readers"].items():
        assert path.is_file(), name
    want = FL_NUMBERS | TRANSPORT_NUMBERS
    if c["traffic"]["server"]["stochastic"]:
        want |= {"arrival_z"}
    assert any(set(c["limits"]) == want | loss for loss in LOSS_NUMBERS), sorted(c["limits"])
    from harness import faults

    assert set(c["faults"]) <= set(faults.FAULTS)
    reported = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2 and c["per_layer"]
    for m in c["per_layer"]:
        assert m["moves"] in reported, (cell, m["name"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_links_and_tcp_presets_state_what_the_flow_reference_reads():
    from test_bench_correct import _flow_reference

    ref = _flow_reference()
    for kind, keys in (("links", ref.LINK_KEYS), ("tcp", ref.TCP_KEYS)):
        files = sorted((BENCH / kind).glob("*.json"))
        assert files
        for f in files:
            assert set(keys) <= set(json.loads(f.read_text())), f


@pytest.mark.parametrize("cell", CELLS)
def test_task_matches_the_configuration(cell):
    """The program's task has the configuration's layers, count and dtype;
    a configuration that states other layers is refused."""
    run = load_run()
    c = run.resolve(run.load_manifest(), cell)
    from harness import traffic

    ref = run._module(c["config_dir"] / c["config"]["reference"])
    task = traffic.make_task(c["config"], ref.init_from_key)
    assert task.init_fn is ref.init_from_key
    layers = c["config"]["layers"]
    missing = {k: v for k, v in layers.items() if not k.startswith("fc2/")}
    for wrong in (dict(layers, **{"fc1/w": [1568, 64]}), missing,
                  dict(layers, **{"fc3/w": [10, 10]}), dict(layers, **{"fc1/b": [64]})):
        with pytest.raises(ValueError):
            traffic.make_task(dict(c["config"], layers=wrong), ref.init_from_key)
    for key, value in (("params", 206921), ("dtype", "bfloat16")):
        with pytest.raises(ValueError):
            traffic.make_task(dict(c["config"], **{key: value}), ref.init_from_key)


def test_config_files_are_distinct_and_under_paths():
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith("bench/") and (ROOT / f).is_file()


@pytest.mark.parametrize("cell,points", [
    ("testbed.fig4_device", 18), ("testbed.fig3_analytic", 20), ("xdev.goal200", 260)])
def test_traffic_sizes(cell, points):
    run = load_run()
    c = run.resolve(run.load_manifest(), cell)
    from harness import traffic

    t = traffic.build(c["config"], c["traffic"], 2**31 + 5)
    if t.engine == "grid":
        pts = t.points(0)
        assert len(pts) == points
        assert all(len(p.clients) == c["config"]["n_clients"] for p in pts)
        seeds = t.seeds(1)
        assert len(set(seeds)) == (1 if c["traffic"]["point_seeds"] == "shared" else points)
    else:
        assert t.selected == points
        shard = t.shard(999_999)
        assert shard["images"].shape == (c["config"]["examples_per_client"], 28, 28, 1)
        assert shard["labels"].shape == (c["config"]["examples_per_client"],)


def test_split_metric_names_share_a_reading():
    """``<metric>.<group>``, a metric split by the cells that report it, is
    read by ``<metric>``'s reader unless it has one of its own."""
    run = load_run()
    known = {"fit.device_ms_per_round", "client_rounds_per_s"}
    assert run.by_name("fit.device_ms_per_round", known) == "fit.device_ms_per_round"
    assert run.by_name("fit.device_ms_per_round.population", known) == "fit.device_ms_per_round"
    assert run.by_name("client_rounds_per_s.population", known) == "client_rounds_per_s"
    with pytest.raises(KeyError):
        run.by_name("fit.other", known)
    for m in MANIFEST["end_to_end"]:
        run.by_name(m["name"], {"client_rounds_per_s", "round_p90_ms", "setup_s"})
