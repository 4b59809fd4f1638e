"""What the correctness check keeps from a run: for each round the FL
reference follows from a point's start, the per-leaf norms of the global
params' change, reduced as the probe captures the round; for a population
window sample, host copies of the last few trees. Driven through the grid
and population paths on the CPU: with the token fixture
(``fixtures/lm_tokens.json``), what the probe and the samples hold stays
within one tree on the device and ``CHECK_ROUNDS + 1`` on the host; with
the CNN, the readings from norms are those of the float64 tree readings
the check made before it kept norms."""

import gc
import json
import sys
from collections import defaultdict

import numpy as np
import pytest

sys.path.insert(0, __import__("os").path.dirname(__file__))
from helpers import BENCH, SECONDS, SEED, load_run, small_cell  # noqa: E402

FIXTURES = BENCH / "tests" / "fixtures"


def _drive(run, cfg, traffic_spec, ref, seconds):
    """One run's engine and window, as ``run_cell`` drives them, without
    the check: the run's samples, its probe and its traffic."""
    from harness import probe as probe_mod
    from harness import traffic as traffic_mod

    traffic = traffic_mod.build(cfg, traffic_spec, SEED)
    task = traffic_mod.make_task(cfg, ref.init_from_key)
    probe = probe_mod.Probe(task, trace=False, capture_rounds=set(range(run.CHECK_ROUNDS)))
    probe.install()
    try:
        drive = run.run_grid if traffic.engine == "grid" else run.run_population
        out = drive(traffic, task, probe, seconds, run.Window(False), SEED)
    finally:
        probe.uninstall()
    assert out.error is None, out.error
    return out, probe, task


# -- the bounds, at a payload other than the CNN's --------------------------------


def _param_bytes(shapes) -> int:
    """Bytes of the live device arrays shaped as a parameter leaf."""
    import jax

    gc.collect()
    want = {(tuple(s.shape), str(s.dtype)) for s in jax.tree.leaves(shapes)}
    return sum(a.nbytes for a in jax.live_arrays() if (a.shape, str(a.dtype)) in want)


@pytest.mark.parametrize("engine", ["grid", "population"])
def test_check_keeps_at_most_the_bounds(engine, monkeypatch):
    import jax

    import repro.core

    run = load_run()
    cfg = json.loads((FIXTURES / "lm_tokens.json").read_text())
    ref = run._module(FIXTURES / cfg["reference"])
    monkeypatch.setattr(repro.core, "fixture_lm_task", ref.task, raising=False)
    if engine == "grid":
        spec = json.loads((BENCH / "traffic" / "fig3_analytic.json").read_text())
        spec.update(values=spec["values"][:1], check_points=2)
        cfg = dict(cfg, rounds=3)
    else:
        spec = json.loads((BENCH / "traffic" / "goal200.json").read_text())
        cfg = dict(cfg, population=1000, goal=4, over_provision=1.3, link="africa_urban",
                   tcp="default")
    out, probe, task = _drive(run, cfg, spec, ref, 4.0)
    shapes = jax.eval_shape(task.init_fn, jax.random.PRNGKey(0))
    tree = probe.tree_bytes
    kept = probe.kept()
    assert kept["tree"] == tree == 4 * cfg["params"]
    assert kept["device"] <= tree and kept["host"] <= (run.CHECK_ROUNDS + 1) * tree

    with_check = _param_bytes(shapes)
    starts = [s[2] for s in out.samples if s[2] is not None]
    captured = {k: sorted(v) for k, v in probe.captured.items()}
    followed = [s[1] for s in out.samples]
    del out, probe
    assert with_check - _param_bytes(shapes) <= tree
    assert sum(sum(l.nbytes for l in jax.tree.leaves(s)) for s in starts) <= (
        run.CHECK_ROUNDS + 1) * tree
    assert all(isinstance(l, np.ndarray) for s in starts for l in jax.tree.leaves(s))
    assert all(rounds == [0, 1, 2] for rounds in captured.values())
    if engine == "grid":  # the points of set-up's sweep and of a window sweep at least
        assert len(captured) >= 2 * len(spec["tcp"]) and kept["host"] == kept["device"] == 0
    else:  # the window ran past what the buffer holds, and a sample was followed in it
        assert len(captured) == 1 and followed[1][0] > run.CHECK_ROUNDS + 1
        assert kept["device"] == tree and kept["host"] == (run.CHECK_ROUNDS + 1) * tree


# -- norms against float64 trees, on the CNN ------------------------------------------


def _tree_norms64(params, base) -> np.ndarray:
    """The check's per-leaf norms as it took them from whole trees, on the
    host in float64."""
    import jax

    return np.array([
        float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(base))
    ])


def _gap64(prog, ref, base, keep) -> float:
    p, r = _tree_norms64(prog, base), _tree_norms64(ref, base)
    denom = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r)[keep] / denom[keep]))


@pytest.mark.parametrize("name", ["testbed.fig4_device", "xdev.goal200"])
def test_norm_readings_equal_tree_readings(name, monkeypatch):
    """Every sample's update and change gaps, from the norms the check
    keeps, against the same gaps from the program's and the reference's
    whole trees in float64. In the population cell every round numbered
    1 mod 3 fails in transport (so a sample holds a failed round, whose
    norms are those of the round before), and the window sample's offset
    reaches past the window, which clips the sample at the window's end."""
    import jax

    from harness import check as chk

    from repro.core.server import FederatedServer

    run, cell = small_cell(name)
    cfg = cell["config"]
    ref = run._module(cell["config_dir"] / cfg["reference"])
    trees = defaultdict(dict)  # point seed -> round -> the params after it
    finish_round = FederatedServer.finish_round

    def keep_tree(srv, job, *a, **kw):
        out = finish_round(srv, job, *a, **kw)
        trees[srv.config.seed][job.rnd] = srv.global_params
        return out

    monkeypatch.setattr(FederatedServer, "finish_round", keep_tree)
    population = cell["traffic"]["engine"] == "population"
    if population:
        finish_transport = FederatedServer.finish_transport

        def fail(srv, pending, completed, *a, **kw):
            if pending.rnd % 3 == 1:
                completed = np.zeros(len(completed), bool)
            return finish_transport(srv, pending, completed, *a, **kw)

        monkeypatch.setattr(FederatedServer, "finish_transport", fail)
        cell["traffic"]["window_sample_rounds"] = 10**6
    out, probe, task = _drive(run, cfg, cell["traffic"], ref, SECONDS[cell["traffic"]["engine"]])
    hp = dict(lr=cfg["lr"], momentum=cfg["momentum"], clip=cfg["clip_norm"])
    assert out.samples and all(s[3] is not None for s in out.samples)
    failed = 0
    for point, rounds, start, prog in out.samples:
        mine = trees[point["seed"]]  # the Fig. 4 points' seeds are spawned: each its own
        if rounds[0] == 0:
            base = task.init_fn(jax.random.PRNGKey(point["seed"]))
        else:
            base = mine[max(r for r in mine if r < rounds[0])]
            assert all(np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(start), jax.tree.leaves(base)))
        ours, prev = [], base
        for r in rounds:
            prev = mine.get(r, prev)
            ours.append(prev)
        replayed = ref.replay(point, rounds, start=start, **hp)
        new = chk.compare(prog, chk.replay_norms(replayed, base), prog["committed"])
        first = prog["committed"].index(True)
        r_first = _tree_norms64(replayed[first]["params"], base)
        keep = r_first >= chk.TINY_LEAF * np.median(r_first)
        for i in range(len(rounds)):
            assert np.allclose(prog["norms"][i], _tree_norms64(ours[i], base),
                               rtol=1e-5, atol=1e-8)
        assert new["update_gap"] == pytest.approx(
            _gap64(ours[first], replayed[first]["params"], base, keep), abs=1e-5)
        assert new["change_gap"] == pytest.approx(
            _gap64(ours[-1], replayed[-1]["params"], base, keep), abs=1e-5)
        assert new["cohort_mismatches"] == 0 and new["loss_gap"] < 0.05
        failed += prog["committed"].count(False)
    if population:
        window = [r for r in trees[out.samples[1][0]["seed"]] if r >= out.samples[1][1][0]]
        assert failed >= 2 and out.samples[1][1][-1] >= max(window)

