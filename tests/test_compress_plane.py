"""Plane-resident compression tests: stacked top-k/int8/bf16 bitwise parity
with sequential per-client compression, residual-digest provenance
coalescing on compressed grids, quantize-kernel round trips (padding, bf16,
zero rows), unique-anchor gather, per-row wire bytes in the transport MC,
and the split-stream stochastic cohort engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chaos import ChaosSchedule
from repro.compress import get_compressor, init_residual_plane
from repro.core import (
    EdgeClient,
    FederatedServer,
    GridPoint,
    ServerConfig,
    fedavg,
    mnist_cnn_task,
    run_fl_grid,
)
from repro.data import make_federated_mnist, synthetic_mnist
from repro.kernels import ops, ref
from repro.transport import DEFAULT, LAB
from repro.transport.des import sim_cohort_round
from repro.utils import tree_stack, tree_unstack

# one shared task so every test reuses the same jit caches
TASK = mnist_cnn_task()
SHARDS = make_federated_mnist(6, 64, seed=0)
EVAL = synthetic_mnist(200, seed=77)

PLANE_COMPRESSORS = ["topk", "int8", "bf16"]


def _server(compressor, *, rounds=2, stochastic=False, rng_streams="single",
            batched=True, seed=0):
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(SHARDS)]
    return FederatedServer(
        TASK,
        clients,
        fedavg(min_fit=0.5),
        tcp=DEFAULT,
        chaos=ChaosSchedule(LAB),
        config=ServerConfig(
            rounds=rounds, local_steps=2, seed=seed, batched=batched,
            stochastic=stochastic, rng_streams=rng_streams,
        ),
        compressor=compressor,
        eval_data=EVAL,
    )


def _point(*, link=LAB, compressor=None, rounds=3, seed=0):
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(SHARDS)]
    return GridPoint(
        clients, fedavg(min_fit=0.5), DEFAULT, ChaosSchedule(link),
        ServerConfig(rounds=rounds, local_steps=2, seed=seed, batched=True),
        compressor=compressor,
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


# ---------------------------------------------------------------------------
# compressor-level bitwise parity (the plane/sequential contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", PLANE_COMPRESSORS)
def test_plane_compressor_bitwise_matches_sequential(name):
    """compress_plane on stacked deltas == compress/decompress client by
    client, bitwise — outputs AND the evolving error-feedback residuals,
    over multiple rounds."""
    comp = get_compressor(name, ratio=0.25)
    key = jax.random.PRNGKey(0)
    deltas = [
        {"w": jax.random.normal(jax.random.fold_in(key, i), (6, 4)),
         "b": jax.random.normal(jax.random.fold_in(key, 100 + i), (7,))}
        for i in range(3)
    ]
    template = jax.tree.map(lambda l: l[0] * 0, tree_stack(deltas))
    slots = [0, 2, 4]  # delivering clients land on arbitrary plane rows
    seq_res = [None] * 5
    plane_res = init_residual_plane(template, 5)
    for rnd in range(3):
        seq_out = []
        for j, s in enumerate(slots):
            payload, seq_res[s] = comp.compress(deltas[j], seq_res[s])
            seq_out.append(comp.decompress(payload))
        plane_out, plane_res = comp.compress_plane(
            tree_stack(deltas), plane_res, jnp.asarray(slots)
        )
        for j, row in enumerate(tree_unstack(plane_out)):
            for a, b in zip(jax.tree.leaves(seq_out[j]), jax.tree.leaves(row)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), (name, rnd, j)
        for s in slots:
            plane_rows = [np.asarray(l)[s] for l in jax.tree.leaves(plane_res)]
            for a, b in zip(jax.tree.leaves(seq_res[s]), plane_rows):
                assert np.array_equal(np.asarray(a).reshape(b.shape), b), (name, rnd, s)


@pytest.mark.parametrize("name", PLANE_COMPRESSORS)
def test_plane_compressor_memo_shares_the_heavy_pass(name):
    """Two residual planes in the same state, compressed under one memo
    key: the second call reuses the memoized ``compress_rows`` result and
    still scatters into its own plane, so both outputs and both new planes
    equal the unmemoized call's, bitwise; a new key computes again."""
    comp = get_compressor(name, ratio=0.25)
    key = jax.random.PRNGKey(1)
    stacked = {"w": jax.random.normal(key, (3, 6, 4)),
               "b": jax.random.normal(jax.random.fold_in(key, 1), (3, 7))}
    template = jax.tree.map(lambda l: l[0] * 0, stacked)
    slots = np.asarray([1, 3, 4], np.int32)
    want_out, want_res = comp.compress_plane(
        stacked, init_residual_plane(template, 5), slots
    )
    memo = {}
    for _ in range(2):
        out, res = comp.compress_plane(
            stacked, init_residual_plane(template, 5), slots, memo, "k"
        )
        assert len(memo) == 1
        for a, b in zip(jax.tree.leaves((want_out, want_res)),
                        jax.tree.leaves((out, res))):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
    comp.compress_plane(stacked, init_residual_plane(template, 5), slots, memo, "k2")
    assert len(memo) == 2


@pytest.mark.parametrize("name", ["topk", "int8"])
def test_batched_plane_compression_matches_unstacked_loop(name):
    """End to end: the batched engine with plane-resident compression
    reproduces the unstacked per-client compression loop EXACTLY
    (History.summary() equality, not a tolerance check)."""
    comp = get_compressor(name, ratio=0.1)
    stripped = dataclasses.replace(comp, compress_plane=None)
    plane = _server(comp).run().summary()
    loop = _server(stripped).run().summary()
    assert _summaries_exactly_equal(plane, loop), (plane, loop)


def test_compressed_rounds_stay_stacked():
    """The plane path never unstacks: no per-client compress calls."""
    comp = get_compressor("topk", ratio=0.1)
    calls = []
    orig = comp.compress
    spy = dataclasses.replace(
        comp, compress=lambda d, r: calls.append(1) or orig(d, r)
    )
    hist = _server(spy).run()
    assert hist.completed_rounds == 2
    assert calls == []  # sequential compress never invoked


# ---------------------------------------------------------------------------
# grid: compressed points share provenance via residual digests
# ---------------------------------------------------------------------------


def test_compressed_grid_matches_per_point_exactly():
    comp = get_compressor("topk", ratio=0.1)
    kwargs = [
        dict(compressor=comp),
        dict(compressor=comp, link=LAB.replace(delay=0.3)),
        dict(compressor=get_compressor("int8")),
        dict(compressor=get_compressor("bf16"), link=LAB.replace(loss=0.15)),
    ]
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    for kw, hist in zip(kwargs, res.histories):
        ref_s = _run_per_point(_point(**kw)).summary()
        assert _summaries_exactly_equal(ref_s, hist.summary()), (kw, ref_s)


def test_compressed_grid_coalesces_with_residual_digest():
    """A compressed pure-latency grid regains full row sharing: one
    trajectory, one eval, ONE heavy compression per round across all
    points (the residual digest keeps compressed points transparent)."""
    comp = get_compressor("int8")
    kwargs = [
        dict(compressor=comp, link=LAB.replace(delay=d)) for d in (0.0, 0.1, 0.5)
    ]
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    s = res.stats
    assert s.fit_rows_total == 3 * s.fit_rows_unique
    assert s.evals_computed * 3 == s.evals_requested
    assert s.compress_requested == 3 * s.compress_computed
    ref_s = _run_per_point(_point(**kwargs[0])).summary()
    for hist in res.histories:
        assert hist.summary()["final_accuracy"] == ref_s["final_accuracy"]


def test_randk_grid_stays_opaque_but_exact():
    """Stateful randk has no plane twin: its points fall back to the
    per-client loop, never share compression, and still reproduce the
    per-point run exactly."""
    kwargs = [dict(compressor=get_compressor("randk", ratio=0.25))]
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    assert res.stats.compress_requested == 0
    ref_s = _run_per_point(
        _point(compressor=get_compressor("randk", ratio=0.25))
    ).summary()
    assert _summaries_exactly_equal(ref_s, res.histories[0].summary())


# ---------------------------------------------------------------------------
# unique-anchor gather
# ---------------------------------------------------------------------------


def test_fit_rows_anchor_gather_bitwise():
    """fit_rows with a shared unique anchor + gather index is bitwise
    identical to per-row anchor stacking."""
    params = TASK.init_fn(jax.random.PRNGKey(0))
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(SHARDS[:4])]
    plans = TASK.plan_fit(clients, 2, np.random.default_rng(3))
    rows = list(zip(clients, plans))
    mus = [0.0] * len(rows)

    per_row, _, _ = TASK.fit_rows([params] * len(rows), rows, 2, mus, False)
    gathered, _, _ = TASK.fit_rows(
        [params], rows, 2, mus, False, anchor_idx=[0] * len(rows)
    )
    for a, b in zip(jax.tree.leaves(per_row), jax.tree.leaves(gathered)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_grid_stacks_unique_anchors_only():
    """A coalescing latency grid stacks O(rounds) anchors, not O(rows)."""
    kwargs = [dict(link=LAB.replace(delay=d)) for d in (0.0, 0.2, 0.8)]
    res = run_fl_grid(TASK, [_point(**kw) for kw in kwargs], eval_data=EVAL)
    s = res.stats
    assert s.anchor_rows_stacked == s.rounds  # one shared anchor per round
    assert s.anchor_rows_stacked < s.fit_rows_unique


# ---------------------------------------------------------------------------
# quantize kernels: row-stacked int8 / bf16 round trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [100, 2048, 2049, 9999])
def test_quantize_rows_kernel_matches_ref(n):
    """Non-tile-multiple widths exercise the pad path; kernel == oracle."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, n)) * 2.5
    scales = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-12) / 127.0
    got = ops.quantize_rows(x, scales, interpret=True)
    expect = ref.quantize_rows_ref(x, scales)
    assert jnp.array_equal(got, expect)
    # round trip bounded by one quantum per row
    deq = got.astype(jnp.float32) * scales[:, None]
    assert float(jnp.max(jnp.abs(deq - x) / scales[:, None])) <= 0.5 + 1e-6


def test_quantize_rows_zero_row():
    """An all-zero row hits the scale clamp and quantizes to exact zeros
    without perturbing its neighbours."""
    x = jnp.stack([jnp.zeros(300), jnp.linspace(-1.0, 1.0, 300)])
    scales = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-12) / 127.0
    q = ops.quantize_rows(x, scales, interpret=True)
    assert not q[0].any()
    assert q[1].any()
    assert jnp.array_equal(q, ref.quantize_rows_ref(x, scales))


@pytest.mark.parametrize("n", [128, 2050])
def test_downcast_bf16_rows_matches_ref(n):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, n))
    got = ops.downcast_bf16_rows(x, interpret=True)
    assert got.dtype == jnp.bfloat16
    assert jnp.array_equal(got, ref.downcast_bf16_rows_ref(x))
    # bf16 round trip is within 1 ulp of the 8-bit mantissa
    back = got.astype(jnp.float32)
    assert float(jnp.max(jnp.abs(back - x))) <= float(jnp.max(jnp.abs(x))) * 2 ** -8


# ---------------------------------------------------------------------------
# wire bytes -> transport
# ---------------------------------------------------------------------------


def test_wire_bytes_exact_and_ordered():
    tree = {"w": jnp.zeros((10000,)), "b": jnp.zeros((50,))}
    topk = get_compressor("topk", ratio=0.01)
    # per-leaf exact: max(n*ratio, 1) kept coords x 8 bytes
    assert topk.wire_bytes(tree) == 8 * (100 + 1)
    none_b = get_compressor("none").wire_bytes(tree)
    bf16_b = get_compressor("bf16").wire_bytes(tree)
    int8_b = get_compressor("int8").wire_bytes(tree)
    assert topk.wire_bytes(tree) < int8_b < bf16_b < none_b


def test_compressed_payload_flows_into_transport():
    """begin_round feeds the compressor's wire size into transport and
    byte accounting — compressed points exchange fewer simulated bytes."""
    comp = get_compressor("topk", ratio=0.01)
    srv = _server(comp)
    job = srv.begin_round(0)
    assert job.payload_bytes == comp.wire_bytes(srv.global_params)
    assert job.payload_bytes < TASK.update_bytes


def test_sim_cohort_round_per_row_bytes():
    """Per-row payload sizes change per-row transfer outcomes: on a clean
    deterministic link a 100x bigger upload takes strictly longer."""
    link = LAB.replace(jitter=0.0, loss=0.0, rate_mbps=10.0)
    out = sim_cohort_round(
        DEFAULT, [link] * 3,
        update_bytes=np.array([50_000, 5_000_000, 50_000]),
        local_train_times=np.full(3, 1.0),
        rng=np.random.default_rng(0),
        connected=np.ones(3, bool),
    )
    assert out.success.all()
    assert out.time[1] > out.time[0]
    assert out.time[0] == out.time[2]
    assert out.bytes_acked[1] == 2 * 5_000_000


# ---------------------------------------------------------------------------
# split-stream stochastic cohort transport
# ---------------------------------------------------------------------------


# The next two tests keep the names they had when the split streams were
# selected as the "fused_transport" engine, so their ids stay stable.
def test_fused_transport_engine_runs_and_is_deterministic():
    """A compressed stochastic cohort on the split streams (per-round
    derived cohort and transport generators) runs and reproduces itself
    bitwise at a fixed seed."""
    comp = get_compressor("topk", ratio=0.1)
    a = _server(comp, stochastic=True, rng_streams="split").run()
    b = _server(comp, stochastic=True, rng_streams="split").run()
    assert a.completed_rounds == 2
    assert _summaries_exactly_equal(a.summary(), b.summary())


def test_fused_transport_models_asymmetric_payloads():
    """The split-stream stochastic cohort sends the compressed payload up
    but the full model down; with a tiny top-k payload the round still
    pays the download."""
    link = LAB.replace(jitter=0.0, rate_mbps=5.0)
    comp = get_compressor("topk", ratio=0.001)
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(SHARDS)]
    srv = FederatedServer(
        TASK, clients, fedavg(min_fit=0.5), tcp=DEFAULT,
        chaos=ChaosSchedule(link),
        config=ServerConfig(rounds=1, local_steps=2, seed=0, batched=True,
                            stochastic=True, rng_streams="split"),
        compressor=comp, eval_data=EVAL,
    )
    hist = srv.run()
    # full-model download at 5 Mbps is ~2.6 s; the compressed-only round
    # time would be far below that
    assert hist.rounds[0].t_end > 2.0
