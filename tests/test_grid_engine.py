"""Scenario-parallel grid engine tests: exact parity with per-point runs,
provenance coalescing, bucketed plane dispatch, chunked unrolling, and the
(S, C) transport grid with sparse traces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chaos import ChaosSchedule, client_failure_schedule
from repro.core import (
    EdgeClient,
    FederatedServer,
    GridPoint,
    ServerConfig,
    fedavg,
    mnist_cnn_task,
    run_fl_grid,
    trimmed_mean,
)
from repro.core.client import PLANE_ROWS, bucket_rows
from repro.utils import tree_stack
from repro.data import make_federated_mnist, synthetic_mnist
from repro.transport import DEFAULT, LAB, TUNED_EDGE, sim_cohort_round, sim_grid_round

# one shared task so every test reuses the same jit caches
TASK = mnist_cnn_task()
SHARDS = make_federated_mnist(6, 64, seed=0)
EVAL = synthetic_mnist(300, seed=77)


def _point(
    *, tcp=DEFAULT, link=LAB, chaos=None, strategy=None, min_fit=0.5, rounds=3,
    seed=0, local_steps=2, stochastic=False, batched=True, rng_streams="single",
    engine="default",
):
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(SHARDS)]
    return GridPoint(
        clients,
        strategy or fedavg(min_fit=min_fit),
        tcp,
        chaos or ChaosSchedule(link),
        ServerConfig(
            rounds=rounds, local_steps=local_steps, seed=seed, batched=batched,
            stochastic=stochastic, rng_streams=rng_streams, engine=engine,
        ),
    )


def _run_per_point(p: GridPoint):
    return FederatedServer(
        TASK, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos, config=p.config,
        eval_data=EVAL,
    ).run()


def _summaries_exactly_equal(a, b):
    for k in a:
        va, vb = a[k], b[k]
        if va != vb and not (va != va and vb != vb):  # nan == nan here
            return False
    return True


# ---------------------------------------------------------------------------
# grid == per-point, exactly (the headline contract)
# ---------------------------------------------------------------------------


def _point_kwargs_matrix():
    return [
        dict(tcp=DEFAULT, link=LAB),
        dict(tcp=TUNED_EDGE, link=LAB),
        dict(tcp=DEFAULT, link=LAB.replace(delay=0.3)),
        dict(tcp=DEFAULT, link=LAB.replace(loss=0.15)),
        dict(tcp=DEFAULT, link=LAB.replace(delay=8.0)),  # dead run -> nan
        dict(tcp=TUNED_EDGE, link=LAB.replace(delay=8.0)),
    ]


def test_grid_matches_per_point_exactly():
    """Every summary field — including the simulated clock and the final
    accuracy — is bitwise identical between the grid engine and per-point
    runs at the same seed. Not a tolerance check."""
    kwargs = _point_kwargs_matrix()
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    for kw, hist in zip(kwargs, res.histories):
        ref = _run_per_point(_point(**kw)).summary()
        got = hist.summary()
        assert _summaries_exactly_equal(ref, got), (kw, ref, got)


def test_grid_matches_per_point_exactly_stochastic():
    """DES transport mode: per-scenario RNG streams are preserved, so even
    event-granular sampling reproduces per-point runs exactly."""
    kwargs = [
        dict(tcp=DEFAULT, link=LAB, stochastic=True),
        dict(tcp=DEFAULT, link=LAB.replace(loss=0.05), stochastic=True),
        dict(tcp=TUNED_EDGE, link=LAB.replace(delay=0.5), stochastic=True),
    ]
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    for kw, hist in zip(kwargs, res.histories):
        ref = _run_per_point(_point(**kw)).summary()
        assert _summaries_exactly_equal(ref, hist.summary()), kw


def test_grid_matches_per_point_with_client_failure_chaos():
    """Chaos-variable cohorts (pod kills) through the grid: still exact."""
    kwargs = [
        dict(chaos=ChaosSchedule(LAB).add(client_failure_schedule(6, f, seed=7)),
             min_fit=0.1)
        for f in (0.0, 0.3, 0.5)
    ]
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    for kw, hist in zip(kwargs, res.histories):
        ref = _run_per_point(_point(**kw)).summary()
        assert _summaries_exactly_equal(ref, hist.summary()), kw


def test_grid_mixed_strategies_exact():
    """Points with different aggregation strategies coexist in one plane
    (different agg fingerprints never coalesce downstream state)."""
    kwargs = [
        dict(strategy=fedavg(min_fit=0.5)),
        dict(strategy=trimmed_mean(0.2, min_fit=0.5)),
    ]
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    for kw, hist in zip(kwargs, res.histories):
        ref = _run_per_point(_point(**kw)).summary()
        assert _summaries_exactly_equal(ref, hist.summary()), kw


# ---------------------------------------------------------------------------
# coalescing and eval memoization
# ---------------------------------------------------------------------------


def test_grid_coalesces_shared_trajectories():
    """Sweep points whose round inputs coincide share plane rows and eval:
    a pure-latency grid (transport times change, gradients don't) computes
    ONE trajectory."""
    kwargs = [
        dict(tcp=DEFAULT, link=LAB.replace(delay=d)) for d in (0.0, 0.1, 0.3, 1.0)
    ]
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    s = res.stats
    assert s.fit_rows_total == 4 * s.fit_rows_unique  # 4 points, 1 trajectory
    assert s.evals_computed * 4 == s.evals_requested
    # and the shared trajectory is the per-point one
    ref = _run_per_point(_point(**kwargs[0])).summary()
    for hist in res.histories:
        assert hist.summary()["final_accuracy"] == ref["final_accuracy"]


def test_grid_coalescing_off_still_exact():
    kwargs = [dict(tcp=DEFAULT, link=LAB)] * 2
    res = run_fl_grid(
        TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL, coalesce=False
    )
    assert res.stats.fit_rows_unique == res.stats.fit_rows_total
    ref = _run_per_point(_point(**kwargs[0])).summary()
    for hist in res.histories:
        assert _summaries_exactly_equal(ref, hist.summary())


# ---------------------------------------------------------------------------
# plane mechanics: row independence, bucketing, chunked unroll
# ---------------------------------------------------------------------------


def test_plane_rows_width_and_position_independent():
    """A row's delta is bitwise identical regardless of plane width or row
    position — the property that makes grid results exactly reproduce
    per-point runs no matter how rows are grouped."""
    params = TASK.init_fn(jax.random.PRNGKey(0))
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(SHARDS)]
    plans = TASK.plan_fit(clients, 2, np.random.default_rng(3))
    rows = list(zip(clients, plans))
    anchors = [params] * len(rows)
    mus = [0.0] * len(rows)

    plane_all, _, _ = TASK.fit_rows(anchors, rows, 2, mus, False)
    plane_tail, _, _ = TASK.fit_rows(anchors[3:], rows[3:], 2, mus[3:], False)
    for a, b in zip(jax.tree.leaves(plane_tail), jax.tree.leaves(plane_all)):
        assert np.array_equal(np.asarray(a[:3]), np.asarray(b[3:6]))


def test_bucket_rows_ladder():
    """Planes pad to whole blocks of PLANE_ROWS rows."""
    assert bucket_rows(1) == PLANE_ROWS
    assert bucket_rows(PLANE_ROWS) == PLANE_ROWS
    assert bucket_rows(PLANE_ROWS + 1) == 2 * PLANE_ROWS
    for n in range(1, 200):
        assert bucket_rows(n) >= n and bucket_rows(n) % PLANE_ROWS == 0
        assert bucket_rows(n) - n < PLANE_ROWS


def test_plane_dispatches_use_bucket_widths():
    """Chaos-variable cohort sizes all dispatch PLANE_ROWS-row blocks: one
    compiled program width for every client-failure sweep."""
    before = len(TASK.plane_dispatch_widths())
    kwargs = [
        dict(chaos=ChaosSchedule(LAB).add(client_failure_schedule(6, f, seed=11)),
             min_fit=0.1)
        for f in (0.0, 0.2, 0.4, 0.6)
    ]
    run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    widths = TASK.plane_dispatch_widths()[before:]
    assert widths, "plane path did not run"
    assert set(widths) == {PLANE_ROWS}, widths


def test_chunked_unroll_long_epochs_matches_sequential():
    """Past _UNROLL_LIMIT the plane runs donated fused chunks; the batched
    fit still tracks the sequential per-client trajectory and consumes the
    RNG stream identically."""
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(SHARDS[:2])]
    params = TASK.init_fn(jax.random.PRNGKey(1))
    steps = 20  # > _UNROLL_LIMIT(16): 2 full chunks of 8 + remainder 4
    r_bat, r_seq = np.random.default_rng(5), np.random.default_rng(5)
    stacked, weights, metrics = TASK.batched_local_fit(params, clients, steps, r_bat, 0.0)
    for i, client in enumerate(clients):
        d, n_ex, m = TASK.local_fit(params, client, steps, r_seq, 0.0)
        assert weights[i] == n_ex
        for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(d)):
            assert float(jnp.max(jnp.abs(a[i] - b))) < 5e-4
        assert abs(metrics[i]["loss"] - m["loss"]) < 1e-3
    assert r_bat.integers(0, 2**31) == r_seq.integers(0, 2**31)


def _block_rows(task, steps, seed):
    clients = [EdgeClient(i, dataset=SHARDS[i % len(SHARDS)]) for i in range(PLANE_ROWS)]
    return list(zip(clients, task.plan_fit(clients, steps, np.random.default_rng(seed))))


def _distinct_anchors(n):
    base = TASK.init_fn(jax.random.PRNGKey(2))
    return [jax.tree.map(lambda l, k=k: l + 0.01 * k, base) for k in range(n)]


@pytest.mark.parametrize("steps", [4, 20])  # fused; chunked past _UNROLL_LIMIT
@pytest.mark.parametrize("n_anchors", [1, 3, 8])
def test_anchor_table_stacked_in_program_matches_eager_stack(n_anchors, steps):
    """The fit program stacks a block's table of distinct anchors and
    gathers each row's anchor itself. Its plane equals, bitwise, the plane
    from an eager tree_stack of the same table, gathered per row on the
    host and fed to the runner with the identity index."""
    rows = _block_rows(TASK, steps, seed=4)
    anchors = _distinct_anchors(n_anchors)
    idx = [i % n_anchors for i in range(PLANE_ROWS)]
    mus = [0.01] * PLANE_ROWS
    plane, _, metrics = TASK.fit_rows(anchors, rows, steps, mus, True, anchor_idx=idx)

    eager = tree_stack(anchors)
    per_row = [jax.tree.map(lambda l, i=i: l[i], eager) for i in idx]
    ref, _, ref_metrics = TASK.fit_rows(per_row, rows, steps, mus, True)
    for a, b in zip(jax.tree.leaves(plane), jax.tree.leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert metrics == ref_metrics


def test_anchor_table_keeps_one_fit_program():
    """Blocks with 1, 2 and 8 distinct anchors share one compiled fit_fused
    program at one (steps, use_prox), and a repeated fit_rows call compiles
    nothing: counted by jax.monitoring backend-compile events, as the
    benchmark's jit.compiles_in_window counts them."""
    task = mnist_cnn_task()  # fresh jit caches
    rows = _block_rows(task, 1, seed=6)
    mus = [0.0] * PLANE_ROWS
    calls = [
        (_distinct_anchors(n), [i % n for i in range(PLANE_ROWS)]) for n in (1, 2, 8)
    ]
    compiled = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for anchors, idx in calls:
            task.fit_rows(anchors, rows, 1, mus, False, anchor_idx=idx)
        assert compiled == ["jit(fit_fused)"], compiled
        compiled.clear()
        anchors, idx = calls[1]
        task.fit_rows(anchors, rows, 1, mus, False, anchor_idx=idx)
        assert compiled == []
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert task.plane_dispatch_widths() == [PLANE_ROWS] * 4


# ---------------------------------------------------------------------------
# per-point row gather: one program, bitwise the eager per-leaf gather
# ---------------------------------------------------------------------------


def _eager_gather_rows(planes, chunk, idxs):
    """The per-leaf eager gather the grid ran before its one-program
    gather: one numpy-indexed take per leaf and segment, then an eager
    concatenate across segments."""
    segments = [[idxs[0]]]
    for k in idxs[1:]:
        if k // chunk == segments[-1][-1] // chunk:
            segments[-1].append(k)
        else:
            segments.append([k])
    trees, n_out, m_out = [], [], []
    for seg in segments:
        ci = seg[0] // chunk
        plane, n_ex, mets = planes[ci]
        lis = [k - ci * chunk for k in seg]
        trees.append(jax.tree.map(lambda l: l[np.asarray(lis)], plane))
        n_out += [n_ex[li] for li in lis]
        m_out += [mets[li] for li in lis]
    if len(trees) == 1:
        return trees[0], n_out, m_out
    return jax.tree.map(lambda *ls: jnp.concatenate(ls, axis=0), *trees), n_out, m_out


def _chunk_planes(template, chunk, n_rows, seed):
    """Per-chunk (plane, n_ex, metrics) triples of ``n_rows`` rows in
    chunks of ``chunk``, each plane padded to ``bucket_rows`` like the fit
    planes; leaves carry the ``template`` tree's shapes."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(0, n_rows, chunk):
        n = min(chunk, n_rows - s)
        plane = jax.tree.map(
            lambda l: jnp.asarray(
                rng.standard_normal((bucket_rows(n),) + l.shape).astype(l.dtype)
            ),
            template,
        )
        out.append((plane, [s + i for i in range(n)],
                    [{"loss": float(s + i)} for i in range(n)]))
    return out


@pytest.mark.parametrize(
    "chunk, n_rows, idxs",
    [
        (64, 64, [3, 7, 9, 20, 41]),  # one segment
        (64, 112, [60, 61, 62, 63, 64, 65, 70]),  # straddles two 64-row chunks
        (16, 40, [14, 15, 16, 20, 31, 32, 35]),  # three chunks
        (64, 112, [70]),  # D = 1
        (64, 112, [3, 3, 7, 7, 7, 64, 64]),  # coalesced rows repeat
        (64, 112, [2, 66, 5, 70, 1]),  # leaves chunk 0 and comes back
    ],
    ids=["one_segment", "two_chunks", "three_chunks", "d1", "duplicates", "non_monotone"],
)
def test_gather_rows_matches_eager_gather(chunk, n_rows, idxs):
    """The one-program gather returns, bitwise and in delivery order, the
    rows the eager per-leaf gather returns, with the same host lists."""
    from repro.core.grid import _gather_rows

    planes = _chunk_planes(TASK.init_fn(jax.random.PRNGKey(0)), chunk, n_rows, seed=len(idxs))
    got, n_ex, mets = _gather_rows(planes, chunk, idxs)
    ref, ref_n_ex, ref_mets = _eager_gather_rows(planes, chunk, idxs)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.tobytes() == b.tobytes()
    assert n_ex == ref_n_ex == idxs
    assert mets == ref_mets


def test_gather_rows_is_one_program():
    """A point's gather is one compiled program, also across chunks, and a
    repeat with the same shapes compiles nothing. In a grid run,
    GridStats.gather_dispatches counts one gather per (point, round) pair
    whose round placed rows."""
    from repro.core.grid import _gather_rows

    compiled = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    # leaf shapes no other test gathers, so the first call compiles
    template = {"a": jnp.zeros((3, 5)), "b": {"c": jnp.zeros((7,))}}
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        _gather_rows(_chunk_planes(template, 64, 112, seed=1), 64, [60, 70, 2])
        assert compiled == ["jit(_gather_program)"], compiled
        compiled.clear()
        _gather_rows(_chunk_planes(template, 64, 112, seed=2), 64, [1, 90, 64])
        assert compiled == []
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)

    kwargs = [
        dict(tcp=DEFAULT, link=LAB),
        dict(tcp=DEFAULT, link=LAB.replace(loss=0.15)),
        dict(tcp=DEFAULT, link=LAB.replace(delay=8.0)),  # no deliveries
    ]
    res = run_fl_grid(TASK, [_point(rounds=2, **kw) for kw in kwargs], eval_data=EVAL)
    with_rows = sum(
        1 for h in res.histories for r in h.rounds if r.delivered and not r.failed_round
    )
    assert 0 < with_rows < 2 * len(kwargs)
    assert res.stats.gather_dispatches == with_rows


# ---------------------------------------------------------------------------
# (S, C) transport grid + sparse traces
# ---------------------------------------------------------------------------


def test_sim_grid_round_parity_mode_matches_cohort():
    """rngs= mode: per-scenario streams reproduce per-scenario
    sim_cohort_round calls bit for bit."""
    links = [
        [LAB, LAB.replace(loss=0.05), LAB.replace(delay=0.3)],
        [LAB.replace(delay=6.0)] * 3,
    ]
    ltt = np.full((2, 3), 10.0)
    conn = np.zeros((2, 3), bool)
    out = sim_grid_round(
        [DEFAULT, TUNED_EDGE], links, update_bytes=100_000,
        local_train_times=ltt, connected=conn,
        rngs=[np.random.default_rng(0), np.random.default_rng(0)], trace=True,
    )
    for s, tcp in enumerate((DEFAULT, TUNED_EDGE)):
        ref = sim_cohort_round(
            tcp, links[s], update_bytes=100_000, local_train_times=ltt[s],
            rng=np.random.default_rng(0), connected=conn[s], trace=True,
        )
        assert np.array_equal(out.success[s], ref.success)
        assert np.allclose(out.time[s], ref.time)
        for f in ref.trace:
            assert np.array_equal(out.trace[f][s], ref.trace[f])


def test_sim_grid_round_fused_mode_per_row_tcp():
    """rng= mode: one lockstep pass over the [S*C] plane with per-row TCP
    params. The default handshake budget dies at 6 s OWD, the tuned one
    survives — inside one fused call."""
    link = LAB.replace(delay=6.0)
    out = sim_grid_round(
        [DEFAULT, TUNED_EDGE], [[link] * 4, [link] * 4], update_bytes=50_000,
        local_train_times=np.full((2, 4), 5.0), connected=np.zeros((2, 4), bool),
        rng=np.random.default_rng(3), trace=True,
    )
    assert not out.success[0].any()
    assert out.success[1].all()
    assert out.trace["syn_attempts"].shape == (2, 4)
    # same seed, same call => deterministic
    out2 = sim_grid_round(
        [DEFAULT, TUNED_EDGE], [[link] * 4, [link] * 4], update_bytes=50_000,
        local_train_times=np.full((2, 4), 5.0), connected=np.zeros((2, 4), bool),
        rng=np.random.default_rng(3), trace=True,
    )
    assert np.allclose(out.time, out2.time)


def test_cohort_trace_keepalive_counts_deterministic():
    """On a clean link the sparse trace is exact: probe count follows the
    keepalive schedule, and a 7200 s keepalive_time past the middlebox
    timeout is silently reaped (the paper's burst-idle pathology)."""
    idle = 900.0
    probing = DEFAULT.replace(tcp_keepalive_time=60.0, tcp_keepalive_intvl=75.0)
    out = sim_cohort_round(
        probing, [LAB] * 3, update_bytes=10_000,
        local_train_times=np.full(3, idle), rng=np.random.default_rng(0),
        connected=np.ones(3, bool), trace=True,
    )
    # probes at 60, 135, ..., <= 900 -> 12 probes; lossless => no failures
    expected = len(np.arange(60.0, idle + 1e-9, 75.0))
    assert np.array_equal(out.trace["keepalive_probes"], np.full(3, expected))
    assert np.array_equal(out.trace["keepalive_failures"], np.zeros(3))
    assert np.array_equal(out.trace["mbox_drops"], np.zeros(3))

    reaped = sim_cohort_round(
        DEFAULT, [LAB] * 3, update_bytes=10_000,  # keepalive_time 7200 > idle
        local_train_times=np.full(3, idle), rng=np.random.default_rng(0),
        connected=np.ones(3, bool), trace=True,
    )
    assert np.array_equal(reaped.trace["mbox_drops"], np.ones(3))
    assert np.array_equal(reaped.trace["keepalive_probes"], np.zeros(3))
    assert (reaped.reconnects >= 1).all()  # discovered dead -> reconnect


def test_trace_disabled_by_default():
    out = sim_cohort_round(
        DEFAULT, [LAB] * 2, update_bytes=10_000,
        local_train_times=np.full(2, 5.0), rng=np.random.default_rng(0),
        connected=np.ones(2, bool),
    )
    assert out.trace is None


def test_strategy_fingerprints_distinguish_factories():
    assert fedavg().agg_fingerprint == fedavg(min_fit=0.1).agg_fingerprint
    assert trimmed_mean(0.1).agg_fingerprint != trimmed_mean(0.2).agg_fingerprint


# ---------------------------------------------------------------------------
# RNG stream split + fused grid transport plane
# ---------------------------------------------------------------------------

# Selection draws of the PRE-SPLIT engine at seed 0 (captured before the
# begin_round split landed): 6 clients, fedavg(min_fit=0.5), DEFAULT/LAB,
# rounds=3, local_steps=2, batched=True. The single-stream ("legacy")
# discipline interleaves selection, transport, and plan draws on one
# generator, so the stochastic rounds 1-2 differ from analytic — exactly
# the coupling rng_streams="split" removes. This regression pins the
# default path to the historical stream bit for bit.
_PRE_SPLIT_SELECTION = {
    False: [[2, 1, 3, 4, 5, 0], [3, 4, 2, 5, 0, 1], [2, 3, 4, 1, 5, 0]],
    True: [[2, 1, 3, 4, 5, 0], [0, 3, 4, 1, 2, 5], [0, 2, 4, 3, 5, 1]],
}


def _selected_ids(history):
    return [r.selected_ids for r in history.rounds]


@pytest.mark.parametrize("stochastic", [False, True])
def test_selection_stream_regression_vs_pre_split_engine(stochastic):
    """The default single-stream engine still consumes the seed's RNG
    stream exactly as every release before the begin_round split."""
    hist = _run_per_point(_point(stochastic=stochastic))
    assert _selected_ids(hist) == _PRE_SPLIT_SELECTION[stochastic]


def test_split_streams_selection_invariant_across_transport_engines():
    """rng_streams="split": the per-round derived cohort stream makes the
    selection sequence bitwise identical no matter which engine samples
    transport — per-point default, per-point fused_transport (S=1 plane),
    grid parity plane, or the grid's shared-rng fused plane."""
    base = dict(stochastic=True, rng_streams="split", link=LAB.replace(loss=0.05))
    ref = _selected_ids(_run_per_point(_point(**base)))
    assert ref  # non-degenerate: rounds actually ran

    alt = _selected_ids(_run_per_point(_point(**base, engine="fused_transport")))
    assert alt == ref

    for mode in ("parity", "fused"):
        res = run_fl_grid(
            TASK, [_point(**base)], eval_data=EVAL, transport=mode
        )
        assert _selected_ids(res.histories[0]) == ref, mode


def test_fused_grid_parity_mode_matches_per_point():
    """transport="parity": ONE sim_grid_round per round covering every
    point's cohort, each scenario on its point's own derived stream —
    bitwise identical History to standalone per-point runs (the
    per-scenario-rng contract), including through ragged chaos cohorts."""
    kwargs = [
        dict(stochastic=True, rng_streams="split"),
        dict(stochastic=True, rng_streams="split", link=LAB.replace(loss=0.05)),
        dict(stochastic=True, rng_streams="split", tcp=TUNED_EDGE,
             link=LAB.replace(delay=0.5)),
        dict(stochastic=True, rng_streams="split", min_fit=0.1,
             chaos=ChaosSchedule(LAB).add(client_failure_schedule(6, 0.4, seed=7))),
    ]
    res = run_fl_grid(
        TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL, transport="parity"
    )
    assert res.stats.transport_dispatches == 3  # one hoisted call per round
    assert res.stats.transport_rows > 0
    for kw, hist in zip(kwargs, res.histories):
        ref = _run_per_point(_point(**kw)).summary()
        assert _summaries_exactly_equal(ref, hist.summary()), kw


def test_fused_grid_shared_stream_deterministic():
    """transport="fused": the shared-rng plane is deterministic run to run
    and counts its dispatches; per-point outcomes are a different draw
    order (distribution-equivalent), so no bitwise claim is made there."""
    kwargs = [
        dict(stochastic=True, rng_streams="split"),
        dict(stochastic=True, rng_streams="split", link=LAB.replace(loss=0.1)),
    ]
    a = run_fl_grid(
        TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL, transport="fused"
    )
    b = run_fl_grid(
        TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL, transport="fused"
    )
    assert a.stats.transport_dispatches == 3
    for ha, hb in zip(a.histories, b.histories):
        assert _summaries_exactly_equal(ha.summary(), hb.summary())


def test_per_point_transport_mode_ignores_hoist_ineligible_points():
    """Analytic and single-stream points fall back to per-point transport
    transparently inside a hoisted grid — results stay exact."""
    kwargs = [
        dict(),  # analytic, single-stream: never hoisted
        dict(stochastic=True),  # stochastic but single-stream: not hoisted
        dict(stochastic=True, rng_streams="split"),  # hoisted
    ]
    res = run_fl_grid(
        TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL, transport="fused"
    )
    for kw, hist in zip(kwargs[:2], res.histories[:2]):
        ref = _run_per_point(_point(**kw)).summary()
        assert _summaries_exactly_equal(ref, hist.summary()), kw


def test_sim_grid_round_ragged_parity_and_mask():
    """Ragged grids (unequal cohort widths): parity mode reproduces
    per-scenario sim_cohort_round calls bit for bit at each scenario's
    true width; the fused mode samples only real rows and marks them."""
    links = [
        [LAB, LAB.replace(loss=0.05)],
        [LAB.replace(delay=0.3)] * 4,
        [LAB],
    ]
    sizes = [2, 4, 1]
    ltt = [np.full(c, 5.0) for c in sizes]
    conn = [np.zeros(c, bool) for c in sizes]
    up = [np.full(c, 100_000, np.int64) for c in sizes]
    down = [np.full(c, 400_000, np.int64) for c in sizes]
    out = sim_grid_round(
        [DEFAULT, TUNED_EDGE, DEFAULT], links, update_bytes=up,
        download_bytes=down, local_train_times=ltt, connected=conn,
        rngs=[np.random.default_rng(s) for s in range(3)],
    )
    assert out.mask.tolist() == [
        [True, True, False, False],
        [True, True, True, True],
        [True, False, False, False],
    ]
    for s, tcp in enumerate((DEFAULT, TUNED_EDGE, DEFAULT)):
        ref = sim_cohort_round(
            tcp, links[s], update_bytes=up[s], local_train_times=ltt[s],
            rng=np.random.default_rng(s), connected=conn[s],
            download_bytes=down[s],
        )
        c = sizes[s]
        assert np.array_equal(out.success[s][:c], ref.success)
        assert np.allclose(out.time[s][:c], ref.time)
        assert not out.success[s][c:].any() and not out.time[s][c:].any()

    fused = sim_grid_round(
        [DEFAULT, TUNED_EDGE, DEFAULT], links, update_bytes=up,
        download_bytes=down, local_train_times=ltt, connected=conn,
        rng=np.random.default_rng(0),
    )
    assert np.array_equal(fused.mask, out.mask)
    assert not fused.time[~fused.mask].any()  # padding never sampled
    fused2 = sim_grid_round(
        [DEFAULT, TUNED_EDGE, DEFAULT], links, update_bytes=up,
        download_bytes=down, local_train_times=ltt, connected=conn,
        rng=np.random.default_rng(0),
    )
    assert np.allclose(fused.time, fused2.time)
