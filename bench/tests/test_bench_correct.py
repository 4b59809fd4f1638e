"""The comparison that decides ``correct``, driven through a whole run of
every cell at a small size on the CPU (the look for a chip is skipped): a
sound run passes the cell's limits, and the control (the reference in
bfloat16 in the program's place) fails them. Besides, the flow reference
against the program's host simulator, and the transport numbers on
outcomes made by hand."""

import sys

import numpy as np
import pytest

sys.path.insert(0, __import__("os").path.dirname(__file__))
from helpers import BENCH, CELLS, small_run  # noqa: E402


@pytest.fixture(scope="module", params=CELLS)
def sound(request):
    return small_run(request.param, control=True)


def test_sound_run_is_correct(sound):
    res, info = sound
    assert res["attempted"] > 0 and res["failed"] == 0 and info.error is None
    assert res["correct"], res["checks"]


def test_control_is_not_correct(sound):
    from harness import check

    _, info = sound
    assert not check.passes(info.control), info.control


def _flow_reference():
    import importlib.util

    spec = importlib.util.spec_from_file_location("flow_reference",
                                                  BENCH / "configs" / "flow_reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("link,tcp,loss,connected", [
    ("lab", "default", 0.3, False), ("lab", "big_buffer", 0.5, True),
    ("lab", "default", 0.55, False), ("africa_urban", "default", None, False)])
def test_flow_reference_agrees_with_the_host_simulator(link, tcp, loss, connected):
    """The chance of completion and the mean duration, against the
    program's event-level simulator on 300 flows."""
    from harness import traffic

    from repro.transport import LinkProfile, TcpParams
    from repro.transport.des import sim_client_round

    lk, tc = traffic.spec("links", link), traffic.spec("tcp", tcp)
    if loss is not None:
        lk = dict(lk, loss=loss)
    nbytes = 4 * 206922
    ref = _flow_reference().completion(lk, tc, down_bytes=nbytes, up_bytes=nbytes, idle_s=2.0,
                                       deadline=600.0, connected=connected, n=3000, seed=3)
    rng = np.random.default_rng(4)
    outs = [sim_client_round(TcpParams(**tc), LinkProfile(**lk), update_bytes=nbytes,
                             local_train_time=2.0, rng=rng, connected=connected)
            for _ in range(300)]
    done = [o.time for o in outs if o.success and o.time <= 600.0]
    assert abs(len(done) / 300 - ref["p"]) < 0.1
    if done and ref["p"] > 0.2:
        assert abs(np.mean(done) - ref["mean_s"]) / ref["mean_s"] < 0.1


def _rec(completed, times, committed, connected=None):
    n = len(completed)
    return {"ids": list(range(100, 100 + n)), "completed": np.array(completed, bool),
            "times": np.array(times, float), "committed": committed,
            "connected": np.zeros(n, bool) if connected is None else np.array(connected, bool)}


def test_commit_errors_by_hand():
    from harness import check

    ok = _rec([1, 1, 0, 1], [3.0, 1.0, 2.0, 2.0], [100, 101, 103])
    assert check.commit_errors([ok], quorum=2, goal=None, deadline=600.0) == 0
    first_two = _rec([1, 1, 0, 1], [3.0, 1.0, 2.0, 2.0], [101, 103])
    assert check.commit_errors([first_two], quorum=2, goal=2, deadline=600.0) == 0
    assert check.commit_errors([ok], quorum=2, goal=2, deadline=600.0) == 1
    late = _rec([1, 1, 1], [700.0, 1.0, 2.0], [101, 102])
    assert check.commit_errors([late], quorum=2, goal=None, deadline=600.0) == 0
    short = _rec([1, 0, 0], [1.0, 2.0, 2.0], None)
    assert check.commit_errors([short], quorum=2, goal=None, deadline=600.0) == 0
    assert check.commit_errors([dict(short, committed=[100])], 2, None, 600.0) == 1


def test_delivery_numbers_by_hand():
    from harness import check

    def ref(connected):
        return {"p": 0.5, "mean_s": 10.0, "var_s": 1.0}

    recs = [_rec([1, 0] * 50, [12.0, 0.0] * 50, None)]
    out = check.delivery(recs, ref, 10**6, 600.0, True)
    assert out["delivery_z"] == pytest.approx(0.0, abs=1e-9)
    assert out["arrival_z"] == pytest.approx(2 / np.sqrt(1 / 50 + 1 / 0.5e6 + 0.1 ** 2))
    every = check.delivery([_rec([1] * 100, [10.0] * 100, None)], ref, 10**6, 600.0, False)
    assert every["delivery_z"] == pytest.approx(50 / np.sqrt(1 + 25 + 1e4 * 0.25 / 1e6))
    assert "arrival_z" not in every


def test_loss_gaps_by_hand():
    """The relative gap grows as the loss falls; the gap in nats does not.
    A replay's round gives its params or its norms against the start."""
    from harness import check

    base = {"w": np.zeros(3, np.float32)}
    step = {"w": np.ones(3, np.float32)}
    ref = check.replay_norms([{"cohort": [1], "params": step, "loss": 2.0},
                              {"cohort": [1], "norms": np.array([3 ** 0.5]), "loss": 0.2}], base)
    assert [list(r["norms"]) for r in ref] == [pytest.approx([3 ** 0.5])] * 2
    prog = {"cohorts": [[1], [1]], "losses": [2.001, 0.201], "norms": [r["norms"] for r in ref]}
    out = check.compare(prog, ref, [True, True])
    assert out["loss_gap_nats"] == pytest.approx(0.001, rel=1e-6)
    assert out["loss_gap"] == pytest.approx(0.005, rel=1e-6)
    assert out["update_gap"] == 0.0 and out["change_gap"] == 0.0
    failed = check.compare(prog, ref, [False, False])
    assert all(np.isinf(failed[k]) for k in check.NAMES if k != "cohort_mismatches")
