"""Fault-domain engine tests: crash-consistent sweep resume (bitwise),
per-point quarantine isolation, server_restart chaos semantics,
retry/backoff reconnect parity (host DES vs device plane), the padded SYN
ladder's width stability, checkpoint dtype round-trips, and the chaos
schedule satellites (partition / internet_shutdown / circuit breaker)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chaos import (
    ChaosSchedule,
    client_failure_schedule,
    internet_shutdown,
    partition,
    server_restart,
)
from repro.checkpoint.store import CheckpointManager, load_tree, save_tree
from repro.compress import get_compressor, randk_compressor, topk_compressor
from repro.core import (
    EdgeClient,
    FederatedServer,
    GridPoint,
    ServerConfig,
    fedavg,
    mnist_cnn_task,
    run_fl_grid,
)
from repro.core.server import _TRANSPORT_STREAM, derive_rng
from repro.data import make_federated_mnist, synthetic_mnist
from repro.transport import (
    DEFAULT,
    LAB,
    TUNED_EDGE,
    RetryPolicy,
    retry_round,
    sim_client_round,
    sim_grid_round,
    sim_grid_round_device,
    transport_plane_key,
)
from repro.transport.model import client_round

TASK = mnist_cnn_task()
SHARDS = make_federated_mnist(6, 64, seed=0)
EVAL = synthetic_mnist(300, seed=77)


def _point(shards=SHARDS, *, comp=None, chaos=None, link=LAB, tcp=DEFAULT,
           strategy=None, **cfg_kw):
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(shards)]
    cfg_kw.setdefault("rounds", 3)
    cfg_kw.setdefault("local_steps", 2)
    cfg_kw.setdefault("seed", 0)
    cfg_kw.setdefault("batched", True)
    return GridPoint(
        clients, strategy or fedavg(min_fit=0.5), tcp,
        chaos or ChaosSchedule(link), ServerConfig(**cfg_kw), compressor=comp,
    )


def _run_per_point(p: GridPoint):
    return FederatedServer(
        TASK, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos, config=p.config,
        compressor=p.compressor, eval_data=EVAL,
    ).run()


def _summaries_exactly_equal(a, b):
    for k in a:
        va, vb = a[k], b[k]
        if va != vb and not (va != va and vb != vb):  # nan == nan here
            return False
    return True


def _assert_histories_identical(ref, got):
    for hr, hg in zip(ref, got):
        assert _summaries_exactly_equal(hr.summary(), hg.summary()), (
            hr.summary(), hg.summary()
        )
        assert len(hr.rounds) == len(hg.rounds)
        for rr, rg in zip(hr.rounds, hg.rounds):
            assert (
                rr.round_idx, rr.t_start, rr.t_end, rr.selected_ids,
                rr.delivered, rr.failed_round, rr.reconnects, rr.cause,
            ) == (
                rg.round_idx, rg.t_start, rg.t_end, rg.selected_ids,
                rg.delivered, rg.failed_round, rg.reconnects, rg.cause,
            )


# ---------------------------------------------------------------------------
# crash-consistent sweeps: kill-and-resume parity (the tentpole contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,extra", [
    ("per_point", dict()),
    ("parity", dict(stochastic=True, rng_streams="split")),
    ("fused", dict(stochastic=True, rng_streams="split")),
])
def test_kill_and_resume_bitwise(tmp_path, mode, extra):
    """A sweep killed after round 2 and resumed from its checkpoint_dir
    produces histories bitwise identical to the uninterrupted run — every
    summary field AND every per-round record, for each transport mode."""
    def pts():
        return [
            _point(rounds=4, **extra),
            _point(rounds=4, link=LAB.replace(delay=0.3), **extra),
        ]

    ref = run_fl_grid(TASK, pts(), eval_data=EVAL, transport=mode)
    d = str(tmp_path / "ckpt")
    part = run_fl_grid(
        TASK, pts(), eval_data=EVAL, transport=mode,
        checkpoint_dir=d, stop_after_round=2,
    )
    assert part.stats.checkpoints_saved == 2
    assert all(len(h.rounds) == 2 for h in part.histories)
    res = run_fl_grid(
        TASK, pts(), eval_data=EVAL, transport=mode, checkpoint_dir=d
    )
    assert res.stats.resumed_round == 2
    _assert_histories_identical(ref.histories, res.histories)


def test_kill_and_resume_bitwise_device_backend(tmp_path):
    """Device-plane transport points resume bitwise too: their streams are
    counter-based per (seed, stream, round), so round-granular restore is
    exact by construction."""
    extra = dict(stochastic=True, transport_backend="device", rounds=4)

    def pts():
        return [_point(**extra), _point(link=LAB.replace(loss=0.05), **extra)]

    ref = run_fl_grid(TASK, pts(), eval_data=EVAL, transport="fused")
    d = str(tmp_path / "ckpt")
    run_fl_grid(
        TASK, pts(), eval_data=EVAL, transport="fused",
        checkpoint_dir=d, stop_after_round=2,
    )
    res = run_fl_grid(TASK, pts(), eval_data=EVAL, transport="fused",
                      checkpoint_dir=d)
    _assert_histories_identical(ref.histories, res.histories)


def test_kill_and_resume_with_residual_plane(tmp_path):
    """Compressed points carry their error-feedback residual plane through
    the checkpoint; the resumed trajectory (which depends on the residual
    bit for bit) still matches the uninterrupted run."""
    def pts():
        return [
            _point(rounds=4, comp=topk_compressor(0.1)),
            _point(rounds=4, comp=topk_compressor(0.1),
                   link=LAB.replace(delay=0.3)),
        ]

    ref = run_fl_grid(TASK, pts(), eval_data=EVAL)
    d = str(tmp_path / "ckpt")
    run_fl_grid(TASK, pts(), eval_data=EVAL, checkpoint_dir=d,
                stop_after_round=2)
    res = run_fl_grid(TASK, pts(), eval_data=EVAL, checkpoint_dir=d)
    _assert_histories_identical(ref.histories, res.histories)


def test_kill_and_resume_sparse_plane_bitwise(tmp_path):
    """Sparse-plane points persist their compacted residual rows plus the
    manifest slot_maps entry; kill-and-resume stays bitwise identical to
    the uninterrupted sparse run (which itself matches dense — see
    tests/test_population_plane.py)."""
    def pts():
        return [
            _point(rounds=4, comp=topk_compressor(0.1), state_plane="sparse"),
            _point(rounds=4, comp=topk_compressor(0.1), state_plane="sparse",
                   link=LAB.replace(delay=0.3)),
        ]

    ref = run_fl_grid(TASK, pts(), eval_data=EVAL)
    d = str(tmp_path / "ckpt")
    run_fl_grid(TASK, pts(), eval_data=EVAL, checkpoint_dir=d,
                stop_after_round=2)
    # the saved manifest carries a first-class slot-map entry per point
    mgr = CheckpointManager(d)
    maps = mgr.slot_maps(mgr.latest_step())
    assert any(k.endswith("/residual") for k in maps), maps
    for v in maps.values():
        assert len(set(v)) == len(v)  # each saved row names a unique slot
    res = run_fl_grid(TASK, pts(), eval_data=EVAL, checkpoint_dir=d)
    _assert_histories_identical(ref.histories, res.histories)


def test_kill_and_resume_cross_storage(tmp_path):
    """A checkpoint written by SPARSE points restores into a DENSE run
    (and bitwise-matches the uninterrupted dense reference): the
    (slot, value) mapping, not the physical row layout, is the checkpoint
    contract."""
    def pts(plane):
        return [_point(rounds=4, comp=topk_compressor(0.1),
                       state_plane=plane)]

    ref = run_fl_grid(TASK, pts("dense"), eval_data=EVAL)
    d = str(tmp_path / "ckpt")
    run_fl_grid(TASK, pts("sparse"), eval_data=EVAL, checkpoint_dir=d,
                stop_after_round=2)
    res = run_fl_grid(TASK, pts("dense"), eval_data=EVAL, checkpoint_dir=d)
    _assert_histories_identical(ref.histories, res.histories)


def test_dense_manifest_back_compat(tmp_path):
    """A pre-sparse checkpoint — no ``slot_maps`` manifest entry, no
    ``residual_plane``/``clients_sparse`` metadata keys — still resumes
    bitwise: readers default every sparse-era field."""
    import json
    import os

    def pts():
        return [_point(rounds=4, comp=topk_compressor(0.1))]

    ref = run_fl_grid(TASK, pts(), eval_data=EVAL)
    d = str(tmp_path / "ckpt")
    run_fl_grid(TASK, pts(), eval_data=EVAL, checkpoint_dir=d,
                stop_after_round=2)
    for step_dir in os.listdir(d):
        if not step_dir.startswith("step_"):
            continue
        mf = os.path.join(d, step_dir, "manifest.json")
        with open(mf) as f:
            manifest = json.load(f)
        manifest.pop("slot_maps", None)
        for mp in manifest["metadata"]["points"]:
            mp.pop("residual_plane", None)
            mp.pop("clients_sparse", None)
        with open(mf, "w") as f:
            json.dump(manifest, f)
    res = run_fl_grid(TASK, pts(), eval_data=EVAL, checkpoint_dir=d)
    _assert_histories_identical(ref.histories, res.histories)


def test_per_point_sparse_population_resume(tmp_path):
    """A single sparse-plane server over a lazy Population checkpoints and
    resumes bitwise through FederatedServer.run(checkpoint_dir=...) — the
    per-point protocol persists only materialized client rows
    (clients_sparse) plus the compacted residual rows."""
    from repro.core import Population
    from repro.data import shard_list_factory

    def srv():
        return FederatedServer(
            TASK, Population(len(SHARDS), shard_list_factory(SHARDS)),
            fedavg(min_fit=0.5), tcp=DEFAULT, chaos=ChaosSchedule(LAB),
            config=ServerConfig(rounds=4, local_steps=2, seed=0,
                                batched=True, state_plane="sparse"),
            compressor=topk_compressor(0.1), eval_data=EVAL,
        )

    ref = srv().run()
    d = str(tmp_path / "ckpt")
    srv().run(checkpoint_dir=d, stop_after_round=2)
    res = srv().run(checkpoint_dir=d)
    _assert_histories_identical([ref], [res])


def test_resume_refuses_mismatched_grid(tmp_path):
    d = str(tmp_path / "ckpt")
    run_fl_grid(TASK, [_point(rounds=3)], eval_data=EVAL, checkpoint_dir=d,
                stop_after_round=1)
    with pytest.raises(ValueError, match="DIFFERENT grid"):
        run_fl_grid(TASK, [_point(rounds=3, seed=1)], eval_data=EVAL,
                    checkpoint_dir=d)


def test_checkpoint_rejects_stateful_compressor_without_accessors(tmp_path):
    """Python-side compressor state is only checkpointable through the
    state_get/state_set accessors (randk ships them — its rotating counter
    rides the manifest, see tests/test_async_engine.py). A stateful
    compressor WITHOUT accessors is refused up front, not corrupted
    later."""
    import dataclasses as _dc

    opaque = _dc.replace(randk_compressor(0.1), state_get=None, state_set=None)
    with pytest.raises(ValueError, match="state_get"):
        run_fl_grid(
            TASK, [_point(comp=opaque)], eval_data=EVAL,
            checkpoint_dir=str(tmp_path / "ckpt"),
        )


def test_checkpoint_store_dtype_roundtrip(tmp_path):
    """bf16 and f16 leaves round-trip BITWISE through save_tree/load_tree
    (bf16 rides as uint16 bits + an orig_dtypes manifest entry; f16 is
    native npz) — the property bitwise sweep resume rests on."""
    tree = {
        "a": jnp.linspace(-3, 3, 17, dtype=jnp.bfloat16),
        "b": jnp.linspace(-3, 3, 17, dtype=jnp.float16),
        "c": jnp.linspace(-3, 3, 17, dtype=jnp.float32),
    }
    d = str(tmp_path / "t")
    save_tree(d, tree)
    loaded, _ = load_tree(d, tree)
    for k in tree:
        a, b = np.asarray(tree[k]), np.asarray(loaded[k])
        assert a.dtype == b.dtype, k
        assert np.array_equal(
            a.view(np.uint8), b.view(np.uint8)
        ), k  # bit-exact, not just value-equal


# ---------------------------------------------------------------------------
# per-point quarantine: one poisoned row never touches the rest of the sweep
# ---------------------------------------------------------------------------


def _poisoned_shards():
    s = SHARDS[2]
    images = s.images.copy()
    images.reshape(-1)[0] = np.nan
    return [dataclasses.replace(s, images=images)] * len(SHARDS)


@pytest.mark.parametrize("comp", [None, "int8"])
def test_quarantine_isolates_poisoned_point(comp):
    """A NaN-poisoned grid point is retired (status "diverged" + cause)
    while every OTHER point's history stays bitwise identical to a run
    without the poisoned point — the failed row never reaches shared
    compression or aggregation state. With int8 error feedback the
    healthy points share one compression pass a round, and the poisoned
    point never requests one."""
    def mk(**kw):
        return _point(comp=comp and get_compressor(comp), **kw)

    links = [LAB, LAB.replace(delay=0.3), LAB.replace(delay=1.0)]
    ref = run_fl_grid(TASK, [mk(link=l) for l in links], eval_data=EVAL)
    got = run_fl_grid(
        TASK,
        [mk(link=links[0]), mk(shards=_poisoned_shards()),
         mk(link=links[1]), mk(link=links[2])],
        eval_data=EVAL,
    )
    bad = got.histories[1]
    assert bad.status == "diverged"
    assert bad.cause in ("non_finite_loss", "non_finite_delta")
    assert bad.rounds[-1].failed_round
    assert got.stats.quarantined == 1
    healthy = [got.histories[0], got.histories[2], got.histories[3]]
    _assert_histories_identical(ref.histories, healthy)
    for a, b in zip(ref.servers, [got.servers[0], *got.servers[2:]]):
        assert all(
            np.array_equal(x, y)
            for x, y in zip(jax.tree.leaves(a.global_params),
                            jax.tree.leaves(b.global_params))
        )
    assert (got.stats.compress_requested, got.stats.compress_computed) == (
        ref.stats.compress_requested, ref.stats.compress_computed
    )
    if comp is not None:
        assert got.stats.compress_computed < got.stats.compress_requested


def test_quarantine_reports_instead_of_raising():
    """Per-point engine: a diverging run terminates with status/cause and
    leaves global params at the round boundary instead of propagating
    non-finite values (or raising) downstream."""
    p = _point(_poisoned_shards())
    srv = FederatedServer(
        TASK, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos,
        config=p.config, eval_data=EVAL,
    )
    before = jax.tree.map(np.asarray, srv.global_params)
    hist = srv.run()
    assert hist.status == "diverged"
    assert hist.cause in ("non_finite_loss", "non_finite_delta")
    assert hist.summary()["status"] == "diverged"
    for a, b in zip(jax.tree.leaves(before),
                    jax.tree.leaves(jax.tree.map(np.asarray, srv.global_params))):
        assert np.array_equal(a, b)


def test_quarantine_opt_out():
    """quarantine=False restores the old behavior: the poison propagates
    (params go non-finite) instead of terminating the point."""
    p = _point(_poisoned_shards(), quarantine=False, rounds=1)
    srv = FederatedServer(
        TASK, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos,
        config=p.config, eval_data=EVAL,
    )
    hist = srv.run()
    assert hist.status == "healthy"  # nobody watched for divergence
    total = sum(float(jnp.sum(l)) for l in jax.tree.leaves(srv.global_params))
    assert not math.isfinite(total)


# ---------------------------------------------------------------------------
# server_restart chaos: mid-training crashes as a scenario axis
# ---------------------------------------------------------------------------


def test_server_restart_loses_round_and_disconnects():
    """A crash inside a round's span fails that round (cause recorded,
    params at the round boundary), drops every client connection, and
    advances the clock to crash + downtime."""
    p = _point(chaos=ChaosSchedule(LAB).add(server_restart(3.0, downtime=50.0)),
               rounds=4)
    srv = FederatedServer(
        TASK, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos,
        config=p.config, eval_data=EVAL,
    )
    hist = srv.run()
    crashed = [r for r in hist.rounds if r.cause == "server_restart"]
    assert len(crashed) == 1
    assert crashed[0].failed_round
    assert crashed[0].t_end >= 3.0 + 50.0
    # rounds after the crash re-handshake (connections were dropped) and
    # proceed healthy
    later = [r for r in hist.rounds if r.round_idx > crashed[0].round_idx]
    assert later and not any(r.failed_round for r in later)


@pytest.mark.parametrize("async_mode", [False, True])
def test_server_restart_in_grid_counts_and_isolates(async_mode):
    """A crash loses exactly one round (sync) or tick (async) of the
    point it hits, matching that point's standalone run, and leaves the
    other point untouched."""
    kw = dict(rounds=4, async_mode=async_mode)
    # the crash lands in the first round, so later rounds show whether the
    # lost round's update leaked into the point's provenance keys
    chaos = ChaosSchedule(LAB).add(server_restart(0.5, downtime=50.0))
    res = run_fl_grid(
        TASK, [_point(chaos=chaos, **kw), _point(**kw)], eval_data=EVAL
    )
    assert res.stats.server_restarts == 1
    assert any(r.cause == "server_restart" for r in res.histories[0].rounds)
    assert not any(r.failed_round for r in res.histories[1].rounds)
    _assert_histories_identical(
        [_run_per_point(_point(chaos=chaos, **kw)), _run_per_point(_point(**kw))],
        res.histories,
    )


def test_divergence_check_runs_once_per_post_phase(monkeypatch):
    """Grid and per-point runs alike run the quarantine scan exactly once
    per point-round, inside that point's finish_round, and never for a
    round that failed before it (the 8 s point fails every round on
    quorum)."""
    finish, scan = FederatedServer.finish_round, FederatedServer._divergence_cause
    posts, scans = [], []

    def finish_round(srv, job, *a, **kw):
        posts.append((id(srv), job.rnd))
        return finish(srv, job, *a, **kw)

    def divergence_cause(srv, *a):
        scans.append((id(srv), posts[-1][1] if posts else None))
        return scan(srv, *a)

    monkeypatch.setattr(FederatedServer, "finish_round", finish_round)
    monkeypatch.setattr(FederatedServer, "_divergence_cause", divergence_cause)
    links = [LAB, LAB.replace(delay=8.0), LAB.replace(loss=0.15)]
    res = run_fl_grid(TASK, [_point(link=l) for l in links], eval_data=EVAL)
    p = _point()
    srv = FederatedServer(
        TASK, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos,
        config=p.config, eval_data=EVAL,
    )
    srv.run()
    assert scans == posts
    for s in [*res.servers, srv]:
        mine = [k for k in posts if k[0] == id(s)]
        assert len(mine) == len(set(mine))
        assert len(mine) == sum(not r.failed_round for r in s.history.rounds)
    assert not any(k[0] == id(res.servers[1]) for k in posts)


@pytest.mark.parametrize("async_mode", [False, True])
@pytest.mark.parametrize("fault", ["none", "server_restart", "quarantine"])
def test_finish_round_reports_whether_the_update_was_taken(async_mode, fault):
    """finish_round returns False when a server_restart inside the round's
    window or a quarantine trigger rejects the round — params stay at the
    round boundary — and True otherwise, in sync rounds and async ticks."""
    chaos = ChaosSchedule(LAB)
    if fault == "server_restart":
        chaos = chaos.add(server_restart(0.5, downtime=10.0))
    shards = _poisoned_shards() if fault == "quarantine" else SHARDS
    p = _point(shards, chaos=chaos, rounds=1, async_mode=async_mode)
    srv = FederatedServer(
        TASK, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos,
        config=p.config, eval_data=EVAL,
    )
    before = jax.tree.map(np.asarray, srv.global_params)
    job = srv.begin_round(0)
    assert job is not None and job.clients
    taken = srv.finish_round(job, *srv.execute_fit(job))
    assert taken == (fault == "none")
    unchanged = all(
        np.array_equal(a, np.asarray(b))
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(srv.global_params))
    )
    assert unchanged != taken
    record = srv.history.rounds[-1]
    assert record.failed_round != taken
    if fault == "server_restart":
        assert record.cause == "server_restart"
    if fault == "quarantine":
        assert srv.history.status == "diverged"


def test_server_restart_in_window_resolution():
    sched = ChaosSchedule(LAB).add(
        server_restart(5.0, downtime=2.0), server_restart(3.0)
    )
    assert sched.server_restart_in(0.0, 10.0) == (3.0, 0.0)
    assert sched.server_restart_in(3.0, 10.0) == (5.0, 2.0)  # half-open left
    assert sched.server_restart_in(5.0, 10.0) is None
    # a server-side fault never masquerades as a link impairment
    assert sched.link_at(3.0, 0) == LAB
    assert sched.alive(3.0, 0)


# ---------------------------------------------------------------------------
# retry/backoff reconnect: policy semantics + host/device parity
# ---------------------------------------------------------------------------


def test_retry_policy_validation_and_backoff():
    rp = RetryPolicy(max_retries=3, base_backoff=2.0, backoff_factor=2.0,
                     max_backoff=6.0)
    assert [rp.backoff(k) for k in (1, 2, 3)] == [2.0, 4.0, 6.0]  # capped
    with pytest.raises(ValueError):
        RetryPolicy(backoff_factor=0.5)
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError):
        ServerConfig(retry=RetryPolicy(), stochastic=False)


def test_retry_degenerate_host_device_exact():
    """loss=0 / jitter=0 with a 6 s OWD link: the SYN ladder deterministically
    exhausts on every attempt, so the retry ladder's clock is closed-form —
    10.5 + (2 + 10.5) + (4 + 10.5) + (8 + 10.5) = 56.0 s — and the host DES,
    the vectorized host grid, and the device plane must agree exactly."""
    link = LAB.replace(delay=6.0)
    rp = RetryPolicy(max_retries=3, base_backoff=2.0, backoff_factor=2.0)
    scalar = sim_client_round(
        DEFAULT, link, update_bytes=100_000, local_train_time=5.0,
        rng=np.random.default_rng(0), connected=False, retry=rp,
    )
    host = sim_grid_round(
        [DEFAULT], [[link] * 3], update_bytes=100_000,
        local_train_times=np.full((1, 3), 5.0),
        connected=np.zeros((1, 3), bool),
        rng=derive_rng(0, _TRANSPORT_STREAM, 0), retry=rp,
    )
    dev = sim_grid_round_device(
        [DEFAULT], [[link] * 3], update_bytes=np.full(1, 100_000, np.int64),
        download_bytes=np.full(1, 100_000, np.int64),
        local_train_times=np.full((1, 3), 5.0),
        connected=np.zeros((1, 3), bool),
        key=transport_plane_key(0, _TRANSPORT_STREAM, 0), retry=rp,
    )
    assert not scalar.success
    assert scalar.time == pytest.approx(56.0)
    assert not host.success.any() and not np.asarray(dev.success).any()
    np.testing.assert_allclose(host.time, np.full((1, 3), 56.0), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(dev.time, np.float64), np.full((1, 3), 56.0), rtol=1e-4
    )


def test_retry_budget_raises_delivery_on_lossy_link():
    """Distributional gate: on a lossy link, a retry budget strictly
    improves pooled delivery rate, host and device agreeing on the
    direction and rough magnitude (the retry-budget frontier, the paper's
    5 s cliff turned into a measurable trade-off)."""
    link = LAB.replace(delay=4.0, loss=0.15)  # near the handshake cliff
    kw = dict(
        update_bytes=np.full(1, 200_000, np.int64),
        download_bytes=np.full(1, 200_000, np.int64),
        local_train_times=np.full((1, 16), 5.0),
        connected=np.zeros((1, 16), bool),
    )
    rates = {}
    for tag, rp in (("none", None), ("r3", RetryPolicy(max_retries=3))):
        h = np.concatenate([
            sim_grid_round(
                [DEFAULT], [[link] * 16],
                rng=derive_rng(0, _TRANSPORT_STREAM, r), retry=rp, **kw
            ).success.ravel()
            for r in range(8)
        ])
        d = np.concatenate([
            np.asarray(sim_grid_round_device(
                [DEFAULT], [[link] * 16],
                key=transport_plane_key(0, _TRANSPORT_STREAM, r), retry=rp,
                **kw
            ).success).ravel()
            for r in range(8)
        ])
        rates[tag] = (h.mean(), d.mean())
    assert rates["r3"][0] > rates["none"][0] + 0.05
    assert rates["r3"][1] > rates["none"][1] + 0.05
    for tag in rates:
        assert abs(rates[tag][0] - rates[tag][1]) < 0.15, (tag, rates)


def test_retry_grid_parity_mode_matches_per_point():
    """transport="parity" with per-point RetryPolicies: the hoisted plane
    threads each point's own policy and stream, so histories stay bitwise
    identical to standalone runs with retry enabled."""
    kws = [
        dict(stochastic=True, rng_streams="split", link=LAB.replace(loss=0.1),
             retry=RetryPolicy(max_retries=2)),
        dict(stochastic=True, rng_streams="split", link=LAB.replace(loss=0.1)),
    ]
    res = run_fl_grid(
        TASK, [_point(**kw) for kw in kws], eval_data=EVAL, transport="parity"
    )
    for kw, hist in zip(kws, res.histories):
        ref = _run_per_point(_point(**kw)).summary()
        assert _summaries_exactly_equal(ref, hist.summary()), kw


def test_retry_round_closed_form_monotone():
    """The analytic composite: completion probability is monotone in the
    retry budget and approaches 1 - (1-p)^(R+1)."""
    link = LAB.replace(loss=0.3)
    base = client_round(DEFAULT, link, update_bytes=300_000,
                        local_train_time=5.0, connected=False)
    prev = base.p_complete
    for R in (1, 2, 4):
        out = retry_round(
            DEFAULT, link, RetryPolicy(max_retries=R),
            update_bytes=300_000, local_train_time=5.0, connected=False,
        )
        assert out.p_complete >= prev
        expect = 1.0 - (1.0 - base.p_complete) ** (R + 1)
        assert out.p_complete == pytest.approx(expect, rel=1e-6)
        prev = out.p_complete
    # a deadline cap of ~0 leaves only the first attempt
    capped = retry_round(
        DEFAULT, link, RetryPolicy(max_retries=4, deadline_cap=0.5),
        update_bytes=300_000, local_train_time=5.0, connected=False,
    )
    assert capped.p_complete == pytest.approx(base.p_complete, rel=1e-6)


# ---------------------------------------------------------------------------
# padded SYN ladder: width-stable compilation across tcp_syn_retries
# ---------------------------------------------------------------------------


def test_pad_attempts_buckets():
    from repro.transport.plane import _pad_attempts

    assert _pad_attempts(1) == 4
    assert _pad_attempts(4) == 4
    assert _pad_attempts(7) == 8  # DEFAULT: syn_retries=6
    assert _pad_attempts(17) == 32  # TUNED_EDGE: syn_retries=16


def test_syn_ladder_width_stable_compilation():
    """Grids mixing different tcp_syn_retries inside one power-of-two
    bucket reuse ONE compiled device program (attempts is a padded static
    arg); the allowed-mask keeps padded attempts inert so outcomes equal
    the host oracle at each point's true ladder depth."""
    from repro.transport.plane import _device_round

    link = LAB.replace(delay=6.0)  # ladder-sensitive: dies iff budget short
    tcps = [DEFAULT.replace(tcp_syn_retries=r) for r in (4, 5, 6)]

    def run(tcp):
        return sim_grid_round_device(
            [tcp], [[link] * 2],
            update_bytes=np.full(1, 50_000, np.int64),
            download_bytes=np.full(1, 50_000, np.int64),
            local_train_times=np.full((1, 2), 5.0),
            connected=np.zeros((1, 2), bool),
            key=transport_plane_key(0, _TRANSPORT_STREAM, 0),
        )

    run(tcps[0])
    before = _device_round._cache_size()
    outs = [run(t) for t in tcps]
    assert _device_round._cache_size() == before  # all pad to 8: no recompile
    # and the mask keeps semantics: deeper ladders buy more budget
    for tcp, out in zip(tcps, outs):
        host = sim_grid_round(
            [tcp], [[link] * 2], update_bytes=50_000,
            local_train_times=np.full((1, 2), 5.0),
            connected=np.zeros((1, 2), bool),
            rng=derive_rng(0, _TRANSPORT_STREAM, 0),
        )
        np.testing.assert_array_equal(host.success, np.asarray(out.success))


# ---------------------------------------------------------------------------
# chaos schedule satellites: event types end-to-end + circuit breaker
# ---------------------------------------------------------------------------


def _mini_server(chaos, *, rounds=4, max_fail=5):
    p = _point(chaos=chaos, rounds=rounds,
               max_consecutive_failures=max_fail)
    return FederatedServer(
        TASK, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos,
        config=p.config, eval_data=EVAL,
    )


def test_partition_fails_rounds_while_active():
    """A full partition of every client makes begin_round record failed
    rounds (no live quorum) for exactly the partitioned span, then
    training resumes."""
    # rounds take ~seconds of sim time; partition the window of round 2
    srv = _mini_server(ChaosSchedule(LAB), rounds=1)
    srv.run()
    t_round = srv.sim_time  # one healthy round's duration
    # active exactly at round 1's start (liveness is resolved at the round
    # boundary) and expired before round 2 begins (a failed round advances
    # the clock by the full deadline)
    chaos = ChaosSchedule(LAB).add(partition(t_round - 1e-6, t_round + 1.0))
    hist = _mini_server(chaos, rounds=3).run()
    causes = [(r.failed_round, r.cause) for r in hist.rounds]
    assert causes[0] == (False, "")
    assert causes[1] == (True, "no_live_quorum")
    assert causes[2] == (False, "")


def test_partial_partition_spares_quorum():
    """Partitioning a sub-quorum subset only shrinks the cohort: the round
    still completes and the victims are excluded from selection."""
    victims = (0, 1)
    chaos = ChaosSchedule(LAB).add(partition(0.0, float("inf"), victims))
    hist = _mini_server(chaos, rounds=2).run()
    assert not any(r.failed_round for r in hist.rounds)
    for r in hist.rounds:
        assert not set(victims) & set(r.selected_ids)


def test_internet_shutdown_trips_circuit_breaker():
    """The paper's state-wide shutdown scenario: with every client
    partitioned indefinitely, the server burns its consecutive-failure
    budget and terminates with status "failed" instead of spinning."""
    chaos = ChaosSchedule(LAB).add(internet_shutdown(0.0, float("inf")))
    hist = _mini_server(chaos, rounds=10, max_fail=3).run()
    assert len(hist.rounds) == 3  # terminated at the breaker, not rounds=10
    assert all(r.failed_round and r.cause == "no_live_quorum"
               for r in hist.rounds)
    assert hist.status == "failed"
    assert hist.cause == "max_consecutive_failures"
    assert hist.summary()["status"] == "failed"


def test_pod_kill_schedule_respects_seed_and_rate():
    ev = client_failure_schedule(10, 0.3, seed=5)
    ev2 = client_failure_schedule(10, 0.3, seed=5)
    assert ev.clients == ev2.clients and len(ev.clients) == 3
    sched = ChaosSchedule(LAB).add(ev)
    assert sched.failed_fraction(1.0, 10) == pytest.approx(0.3)
