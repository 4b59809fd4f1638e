"""Program spans (``repro.utils.spans``) under a profiler session, read
back from the ``.xplane.pb`` the way a trace reader sees them: every
documented span records, fit spans nest in their round, counts ride on the
spans, one ``fl.sync.*`` span per device-to-host read, and tracing leaves
every result bitwise unchanged."""

import dataclasses
import glob
import math
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.chaos import ChaosSchedule
from repro.core import (
    EdgeClient,
    FederatedServer,
    GridPoint,
    Population,
    ServerConfig,
    fedavg,
    mnist_cnn_task,
    run_fl_grid,
)
from repro.core.client import PLANE_ROWS
from repro.data import make_federated_mnist, synthetic_mnist
from repro.data.federated import federated_mnist_factory
from repro.transport import DEFAULT, LAB
from repro.utils import spans

TASK = mnist_cnn_task()
SHARDS = make_federated_mnist(10, 64, seed=0)
EVAL = synthetic_mnist(100, seed=7)
STEPS, BATCH, MAX_PLANE_ROWS = 2, 32, 64
GOAL, POPULATION = 20, 1000


def _grid():
    def point(loss, seed):
        return GridPoint(
            [EdgeClient(i, dataset=s) for i, s in enumerate(SHARDS)],
            fedavg(min_fit=0.5),
            DEFAULT,
            ChaosSchedule(LAB.replace(loss=loss)),
            ServerConfig(rounds=2, local_steps=STEPS, seed=seed, stochastic=True,
                         rng_streams="split", transport_backend="device", batched=True),
        )

    return run_fl_grid(TASK, [point(0.0, 11), point(0.1, 12)], eval_data=EVAL,
                       transport="fused", max_plane_rows=MAX_PLANE_ROWS)


def _population():
    server = FederatedServer(
        TASK,
        Population(POPULATION, federated_mnist_factory(64, seed=3)),
        fedavg(min_fit=GOAL / POPULATION),
        tcp=DEFAULT,
        chaos=ChaosSchedule(LAB.replace(loss=0.05)),
        config=ServerConfig(rounds=2, local_steps=STEPS, seed=5, stochastic=True, batched=True,
                            transport_backend="device", clients_per_round=GOAL / POPULATION,
                            over_provision=1.3, quorum_close_fraction=GOAL / 26),
        eval_data=EVAL,
    )
    server.run()
    return server


def _traced(fn, logdir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(logdir), profiler_options=opts):
        out = fn()
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name.startswith(spans.PREFIX)
    ]
    return out, sorted(events, key=lambda e: e[1])


@pytest.fixture(scope="module")
def grid_runs(tmp_path_factory):
    plain = _grid()
    traced, events = _traced(_grid, tmp_path_factory.mktemp("grid"))
    return plain, traced, events


@pytest.fixture(scope="module")
def population_runs(tmp_path_factory):
    plain = _population()
    traced, events = _traced(_population, tmp_path_factory.mktemp("population"))
    return plain, traced, events


def _documented():
    """Span names of the module's list, and the sync sites it names."""
    names = re.findall(r"^- ``(fl\.[a-z0-9_.]+)``", spans.__doc__, re.M)
    sites = re.search(r"sites (.*)\.$", spans.__doc__, re.M).group(1)
    return set(names), set(re.findall(r"``([a-z_]+)``", sites))


def _rounds(events):
    return [e for e in events if e[0] == "fl.round"]


def _inside(e, outer):
    return outer[1] <= e[1] and e[2] <= outer[2]


def _syncs_by_round(events):
    return [sum(1 for e in events if e[0].startswith("fl.sync.") and _inside(e, r))
            for r in _rounds(events)]


def _assert_histories_bitwise(a, b):
    assert len(a.rounds) == len(b.rounds)
    for ra, rb in zip(a.rounds, b.rounds):
        assert dataclasses.asdict(ra) == dataclasses.asdict(rb)
    assert a.eval_metrics == b.eval_metrics
    assert (a.status, a.cause) == (b.status, b.cause)


def _assert_params_bitwise(pa, pb):
    for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_every_documented_span_records(grid_runs, population_runs):
    names, sites = _documented()
    assert sites == {"fit_metrics", "transport", "divergence", "eval"}
    seen = {e[0] for e in grid_runs[2] + population_runs[2]}
    want = names | {f"fl.sync.{s}" for s in sites}
    assert want <= seen, sorted(want - seen)
    assert seen <= want, sorted(seen - want)  # nothing records that is not documented


@pytest.mark.parametrize("engine", ["grid", "population"])
def test_fit_spans_nest_in_their_round(engine, grid_runs, population_runs):
    events = (grid_runs if engine == "grid" else population_runs)[2]
    rounds = _rounds(events)
    assert [r[3]["round"] for r in rounds] == [0, 1]
    fit = [e for e in events if e[0].startswith("fl.fit.")]
    assert fit
    assert all(any(_inside(e, r) for r in rounds) for e in fit)


@pytest.mark.parametrize("engine", ["grid", "population"])
def test_h2d_bytes_are_the_padded_blocks(engine, grid_runs, population_runs):
    events = (grid_runs if engine == "grid" else population_runs)[2]
    copies = [e for e in events if e[0] == "fl.fit.h2d"]
    dispatches = [e for e in events if e[0] == "fl.fit.dispatch"]
    assert copies and len(copies) == len(dispatches)
    block = PLANE_ROWS * STEPS * BATCH * (28 * 28 * 4 + 4)  # f32 images, i32 labels
    assert {e[3]["bytes"] for e in copies} == {block}
    assert {(e[3]["rows"], e[3]["steps"]) for e in dispatches} == {(PLANE_ROWS, STEPS)}


def test_grid_sync_count_per_round(grid_runs):
    """Per round: one transport read for the shared device plane, one
    fit-metrics read per fit_rows call (``max_plane_rows`` rows each), and
    per point that trained a divergence check and an eval (the seeds
    differ, so nothing coalesces)."""
    _, traced, events = grid_runs
    want, trained_total = [], 0
    for r in range(2):
        trained = [h.rounds[r] for h in traced.histories if not h.rounds[r].failed_round]
        rows = sum(rec.delivered for rec in trained)
        want.append(1 + math.ceil(rows / MAX_PLANE_ROWS) + 2 * len(trained))
        trained_total += len(trained)
    assert _syncs_by_round(events) == want
    assert traced.stats.evals_computed == trained_total


def test_population_sync_count_per_round(population_runs):
    """Per committed round: transport, fit metrics, divergence, eval."""
    _, traced, events = population_runs
    want = [1 if rec.failed_round else 4 for rec in traced.history.rounds]
    assert _syncs_by_round(events) == want
    builds = [e for e in events if e[0] == "fl.shard_build"]
    assert len(builds) == traced.clients.shards_built
    assert {e[3]["examples"] for e in builds} == {64}
    selects = [e for e in events if e[0] == "fl.select"]
    assert [e[3]["cohort"] for e in selects] == [rec.selected for rec in traced.history.rounds]


def test_tracing_leaves_results_bitwise(grid_runs, population_runs):
    plain, traced, _ = grid_runs
    for a, b in zip(plain.histories, traced.histories):
        _assert_histories_bitwise(a, b)
    for a, b in zip(plain.servers, traced.servers):
        _assert_params_bitwise(a.global_params, b.global_params)
    plain, traced, _ = population_runs
    _assert_histories_bitwise(plain.history, traced.history)
    _assert_params_bitwise(plain.global_params, traced.global_params)


def test_to_host_reads_under_its_span(tmp_path):
    x = {"a": jax.numpy.arange(3.0), "b": jax.numpy.ones((2, 2), jax.numpy.int32)}
    got, events = _traced(lambda: spans.to_host(x, "test"), tmp_path)
    assert isinstance(got["a"], np.ndarray) and got["a"].tolist() == [0.0, 1.0, 2.0]
    assert got["b"].dtype == np.int32
    assert [(e[0], e[3]) for e in events] == [("fl.sync.test", {"bytes": 3 * 4 + 4 * 4})]
