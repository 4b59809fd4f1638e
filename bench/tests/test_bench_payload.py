"""What is model-specific in the harness comes from the configuration file
and its reference module: a token configuration with a nested parameter
tree (``fixtures/lm_tokens.json``, the program's ``lm_task`` over
``deepseek_v2_236b.reduced()`` in float32) resolves and builds its task,
shards, eval set, eval count and ``round_mfu`` through the harness as it
stands, and the two CNN configurations build the same data as before
payloads came from the configuration."""

import hashlib
import json
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, __import__("os").path.dirname(__file__))
from helpers import BENCH, MANIFEST, load_run  # noqa: E402

FIXTURES = BENCH / "tests" / "fixtures"
CELL = "fixture.lm_tokens"
SEED = 2**31 + 5
PEAK = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


@pytest.fixture
def fixture_cell(tmp_path, monkeypatch):
    """The fixture configuration as a cell of the Fig. 3 traffic, resolved
    from a benchmark directory that holds the real traffic, metric readers
    and peaks, and a limits file of its own; its payload factory
    registered as ``repro.core.fixture_lm_task``."""
    import repro.core

    run = load_run()
    bench = tmp_path / "bench"
    (bench / "limits").mkdir(parents=True)
    for name in ("traffic", "metrics", "peaks.json"):
        (bench / name).symlink_to(BENCH / name)
    (bench / "limits" / f"{CELL}.json").write_text(
        (BENCH / "limits" / "testbed.fig3_analytic.json").read_text())
    manifest = {
        "configs": [{"name": "lm_tokens", "file": "bench/tests/fixtures/lm_tokens.json"}],
        "workloads": [{"name": CELL, "config": "lm_tokens", "traffic": "fig3_analytic", "chips": 1}],
        "end_to_end": [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"],
        "per_layer": [dict(m, workloads=[CELL]) for m in MANIFEST["per_layer"]
                      if m["name"] == "round_mfu"],
    }
    cell = run.resolve(manifest, CELL, bench)
    ref = run._module(cell["config_dir"] / cell["config"]["reference"])
    monkeypatch.setattr(repro.core, "fixture_lm_task", ref.task, raising=False)
    return run, cell, ref


def test_token_configuration_resolves_and_builds(fixture_cell):
    from harness import traffic

    from repro.data import ClientDataset

    run, cell, ref = fixture_cell
    cfg = cell["config"]
    assert cell["config_dir"] == FIXTURES and cfg["data"] == "tokens"
    assert cell["readers"]["round_mfu"].resolve() == BENCH / "metrics" / "round_mfu.py"
    task = traffic.make_task(cfg, ref.init_from_key)
    assert task.init_fn is ref.init_from_key and task.name == "lm_deepseek-v2-smoke"
    t = traffic.build(cfg, cell["traffic"], SEED)
    n, width = cfg["examples_per_client"], cfg["seq_len"] + 1
    for d in t.datasets:
        assert isinstance(d, ClientDataset) and d.num_examples() == n
        assert d.tokens.shape == (n, width) and d.tokens.dtype == np.int32
        assert 0 <= d.tokens.min() and d.tokens.max() < cfg["vocab_size"]
    assert not np.array_equal(t.datasets[0].tokens, t.datasets[1].tokens)
    plan = t.datasets[0].batch_indices(cfg["batch_size"], cfg["local_steps"],
                                       rng=np.random.default_rng(0))
    assert plan.shape == (cfg["local_steps"], cfg["batch_size"])
    assert set(t.eval_data) == {"tokens"}
    assert t.eval_data["tokens"].shape == (cfg["eval_examples"], width)
    again = traffic.build(cfg, cell["traffic"], SEED)
    assert np.array_equal(again.shard(3)["tokens"], t.shard(3)["tokens"])
    assert np.array_equal(again.eval_data["tokens"], t.eval_data["tokens"])
    assert len(t.points(0)) == 20


def test_token_generator_is_the_programs():
    """The benchmark's copy of the Markov generator draws what
    ``repro.data.tokens`` draws from the same stream."""
    from harness import data

    from repro.data.tokens import _markov_tokens

    for vocab in (512, 12_800):
        ours = data.markov_tokens(np.random.default_rng(vocab), 4000, vocab)
        assert np.array_equal(ours, _markov_tokens(np.random.default_rng(vocab), 4000, vocab))


def test_wrong_leaf_shape_fails_clearly(fixture_cell):
    from harness import traffic

    _, cell, ref = fixture_cell
    cfg = cell["config"]
    wrong = dict(cfg, layers=dict(cfg["layers"], **{"seg1/mlp/w_gate": [2, 8, 64, 48]}))
    with pytest.raises(ValueError, match=r"seg1/mlp/w_gate is \[2, 8, 64, 96\], not \[2, 8, 64, 48\]"):
        traffic.make_task(wrong, ref.init_from_key)
    unknown = dict(cfg, layers=dict(cfg["layers"], **{"seg1/mlp/w_out": [2, 8, 96, 64]}))
    with pytest.raises(ValueError, match=r"no leaf seg1/mlp/w_out"):
        traffic.make_task(unknown, ref.init_from_key)
    partial = {k: v for k, v in cfg["layers"].items() if k != "unembed"}
    with pytest.raises(ValueError, match=r"top-level entries"):
        traffic.make_task(dict(cfg, layers=partial), ref.init_from_key)


@pytest.mark.parametrize("config", ["paper_testbed", "lm_tokens"])
def test_probe_counts_eval_examples_by_leading_axis(config, fixture_cell):
    import jax

    from harness import probe as probe_mod
    from harness import traffic

    run, cell, ref = fixture_cell
    if config != "lm_tokens":
        cell = run.resolve(run.load_manifest(), "testbed.fig3_analytic")
        ref = run._module(cell["config_dir"] / cell["config"]["reference"])
    cfg = cell["config"]
    task = traffic.make_task(cfg, ref.init_from_key)
    t = traffic.build(cfg, cell["traffic"], SEED)
    probe = probe_mod.Probe(task, trace=True, capture_rounds=set())
    probe.install()
    try:
        probe.counting = True
        task.evaluate(task.init_fn(jax.random.PRNGKey(1)), t.eval_data)
    finally:
        probe.uninstall()
    assert probe.counters["eval_examples"] == cfg["eval_examples"]


def test_round_mfu_from_the_configurations_reference(fixture_cell):
    run, cell, ref = fixture_cell
    cfg = cell["config"]
    forward = 2 * 224_256 * 32
    assert ref.flops_per_example(cfg) == {"train": 3 * forward, "eval": forward}
    ctx = SimpleNamespace(counters={"fit_row_steps": 40.0, "eval_examples": 16.0},
                          window_s=2.5, peak=PEAK, config=cfg, reference=ref)
    want = 100.0 * (40 * 4 * 3 * forward + 16 * forward) / (2.5 * PEAK["bf16_flops_per_s"])
    assert run._module(cell["readers"]["round_mfu"]).read(ctx) == pytest.approx(want, rel=1e-12)


def _digest(arrays):
    h = hashlib.sha256()
    for k in ("images", "labels"):
        h.update(np.ascontiguousarray(arrays[k]).tobytes())
    return h.hexdigest()[:16]


# sha256 of images then labels, as the harness built them from seed 2**31 + 5
# before the configuration named its data
PARENT_DIGESTS = {
    "paper_testbed": {
        0: "f4f21ad5f87a9df8", 1: "07f30e77d77ce070", 2: "363852e10a07e81e",
        3: "53a47316fb318db8", 4: "c52866af2cd1a5e0", 5: "4f09bb31883f4a8b",
        6: "54bf4cba7e0b8114", 7: "5984060f6a11cf76", 8: "f8d36765b883167c",
        9: "418f1abc6439d3d6", "eval": "8597f931c01645c6",
    },
    "cross_device": {
        0: "d164f674abcf4951", 7: "047d081b19f2ae0b", 999_999: "7c6abed732b6fe9f",
        "eval": "8597f931c01645c6",
    },
}


@pytest.mark.parametrize("config,cell", [("paper_testbed", "testbed.fig4_device"),
                                         ("cross_device", "xdev.goal200")])
def test_cnn_data_is_bitwise_as_before(config, cell):
    from harness import traffic

    from repro.data import ClientDataset

    run = load_run()
    c = run.resolve(run.load_manifest(), cell)
    t = traffic.build(c["config"], c["traffic"], SEED)
    want = PARENT_DIGESTS[config]
    assert _digest(t.eval_data) == want["eval"]
    assert {k: _digest(t.shard(k)) for k in want if k != "eval"} == {
        k: v for k, v in want.items() if k != "eval"}
    from harness import data

    wrapped = t.datasets[0] if t.engine == "grid" else data.dataset(7, t.shard(7))
    assert type(wrapped) is ClientDataset
    assert wrapped.num_examples() == c["config"]["examples_per_client"]
