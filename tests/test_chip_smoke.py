"""chip_smoke.py's phases at a tiny size on the CPU, and its refusal to
run anywhere but on a TPU."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke as cs  # noqa: E402

TINY = cs.Deployment(
    n_clients=3, examples_per_client=32, rounds=2, local_steps=1,
    eval_examples=64, min_accuracy=0.0,
)


def test_phases_run_at_tiny_size():
    """Off the TPU the kernels run interpreted and the hot path takes the
    XLA references, so the kernel-only checks are off; everything else
    (oracle agreement, rounds, grid == per-point bitwise) is checked."""
    fx = cs.Fixture(TINY)
    k = cs.phase_kernels(fx, rows=(1, 3), interpret=True)
    assert k["leaves"] == 8 and k["reduce_max_rel_err"] <= 1e-6
    assert cs.phase_compress(fx)["rows"] == TINY.n_clients
    for comp in ("int8", "bf16"):
        s = cs.phase_server(fx, comp, kernels=False)
        assert s["completed_rounds"] == TINY.rounds
        assert s["kernel_calls"] == 0 and s["oracle_calls"] > 0
    g = cs.phase_grid(fx)
    assert len(g["points"]) == len(cs.GRID_SPECS)
    f = cs.phase_fused(fx)
    assert f["device_dispatches"] == TINY.rounds


def test_main_refuses_a_cpu(capsys):
    assert cs.main([]) == 2
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert not any(json.loads(l).get("ok") for l in out.splitlines() if l.strip())


def test_hot_path_spy_restores_the_modules():
    before = (cs.ops.quantize_rows, cs.ref.quantize_rows_ref,
              cs.strategy_mod._stacked_mean_xla)
    with pytest.raises(RuntimeError), cs.hot_path_calls():
        raise RuntimeError
    assert before == (cs.ops.quantize_rows, cs.ref.quantize_rows_ref,
                      cs.strategy_mod._stacked_mean_xla)
