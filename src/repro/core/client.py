"""Edge client model: local training payload + resource/connection state.

A client owns (1) a data shard, (2) a compute profile — the paper's
0.5 vCPU Raspberry-Pi-class allocation becomes a ``compute_rate``
multiplier over measured step cost, (3) a transport connection state
(connected / idle-since), and (4) a compression residual (error feedback).

``LocalTask`` abstracts the payload: the paper's MNIST CNN and reduced LM
configs implement the same interface, so every benchmark can swap payloads.

The cohort/scenario hot path is the *plane* formulation: local SGD for any
set of (anchor params, client, batch plan) rows runs as stacked tensor
programs with a leading row axis, ``PLANE_ROWS`` rows per dispatch. Rows
are independent by construction — every cross-row operation is
batch-mapped, never reduced — and every dispatch has the same width, so a
row's result is bitwise identical no matter how rows are grouped into
planes. The batched cohort engine (one scenario, rows = cohort) and the
grid engine (rows = union of cohorts across sweep points, see
``repro.core.grid``) share this runner, which is what makes grid sweeps
exactly reproduce per-point runs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import ClientDataset
from repro.models.cnn import cnn_apply, cnn_init, cnn_loss, cnn_loss_stacked
from repro.optim import (
    apply_updates,
    clip_by_global_norm,
    clip_by_global_norm_stacked,
    sgd,
)
from repro.utils import tree_stack, tree_sub
from repro.utils.spans import span, to_host


@dataclass
class LocalTask:
    """Payload: init + one local-training run on a client shard."""

    name: str
    init_fn: Callable  # key -> params
    local_fit: Callable  # (params, client, steps, rng, prox_mu) -> (delta, n_examples, metrics)
    evaluate: Callable  # (params, data) -> metrics
    update_bytes: int  # uncompressed wire size of one update
    # Cohort-batched twin of local_fit (the vectorized engine's hot path):
    # (params, clients, steps, rng, prox_mu) ->
    #     (stacked_delta [C,...], n_examples [C], metrics [C]).
    # Must consume ``rng`` draw-for-draw identically to calling local_fit on
    # each client in order, so batched/sequential runs share one RNG stream.
    # None => the server falls back to the sequential per-client loop.
    batched_local_fit: Optional[Callable] = None
    # --- scenario-plane API (the grid engine's hot path) -----------------
    # plan_fit(clients, steps, rng) -> per-client batch plans. Consumes
    # ``rng`` exactly like batched_local_fit's drawing phase, so a caller
    # can split "draw plans" from "run rows" without moving the stream.
    plan_fit: Optional[Callable] = None
    # plan_digest(client, plan) -> hashable fingerprint of the training
    # inputs a (client, plan) row contributes; two rows with equal digests
    # and equal anchors compute identical deltas (coalescing key).
    plan_digest: Optional[Callable] = None
    # fit_rows(anchors, rows, steps, mus, use_prox, anchor_idx=None) ->
    #     (plane_delta [Rb,...], n_examples [R], metrics [R]) where
    # rows is a list of R (client, plan) pairs, mus is a list of R prox
    # coefficients, and Rb is R padded up to whole PLANE_ROWS blocks
    # (callers slice/gather the rows they own). ``anchors`` is a list of
    # UNIQUE per-row params pytrees and ``anchor_idx`` maps each row to its
    # anchor — each block passes only the anchors its rows reference, and
    # the fit program stacks and gathers them. ``anchor_idx=None`` means
    # anchors is per-row (len R, identity mapping). One fused dispatch per block
    # (chunked past _UNROLL_LIMIT steps).
    fit_rows: Optional[Callable] = None

    def plane_dispatch_widths(self) -> List[int]:
        """Row widths of every plane dispatch so far (test/bench
        introspection: each is PLANE_ROWS)."""
        runner = getattr(self.fit_rows, "runner", None)
        return list(runner.dispatch_widths) if runner is not None else []


_UNROLL_LIMIT = 16  # local steps fused into one program before chunking
_CHUNK_STEPS = 8  # fused block size for long local epochs (compile-bounded)

# Rows per plane dispatch. A plane runs as blocks of exactly this many rows
# (the last block padded; padding rows are computed and discarded), so one
# program per (steps, prox) serves every cohort and grid width. The fixed
# width is also what keeps a row's result independent of its plane: on a
# TPU v5e, XLA's lowering of the fit program changes the summation order of
# the conv weight-gradient einsum and of the bias-gradient sums with the
# row count, while at a fixed width a row's result depends neither on its
# position nor on its neighbours.
PLANE_ROWS = 8


def bucket_rows(n: int) -> int:
    """Rows a plane of ``n`` rows dispatches: n padded to whole blocks."""
    return -(-n // PLANE_ROWS) * PLANE_ROWS


def _plane_sgd_runner(cohort_loss_fn, lr: float):
    """jit'd plane runner: R independent local-SGD trajectories as stacked
    tensor programs — one fused dispatch per call, no per-row Python loop.

    ``cohort_loss_fn(stacked_params, batch)`` must return per-row losses
    [R] plus a dict of per-row metric arrays, where every params leaf and
    batch leaf carries a leading row axis R. Summing the per-row losses
    before differentiation yields each row's own gradient in its slice
    (rows share no parameters), so one value_and_grad drives R independent
    SGD trajectories. Anchors arrive as a table of exactly PLANE_ROWS
    params trees (the block's UNIQUE anchors, padded by repeating the
    first) plus a per-row gather index [R] (each row may start from
    different global params — the grid engine mixes sweep points in one
    plane — but most blocks reference only 1-3 distinct anchors). The
    table is stacked and gathered into the [R, ...] anchor view inside the
    program, so a block costs no eager device op per leaf, and its fixed
    length keeps one program per (steps, prox); ``mu`` is a per-row prox
    coefficient.
    Clipping is per-row (clip_by_global_norm_stacked); the momentum update
    is leaf-wise and vectorizes over the stacked axis unchanged.

    Lowering notes (CPU-measured, see benchmarks/round_engine_bench.py):
    jax.lax.scan over steps and vmap'd lax.conv both lower catastrophically
    (batched-kernel convs become grouped convs; scan pins them inside a
    while loop), so local steps are UNROLLED at trace time into one fused
    program — XLA then aliases the params/momentum buffers across steps
    instead of round-tripping ~100 MB per step through fresh allocations.
    Beyond _UNROLL_LIMIT steps the unroll is CHUNKED: donated fused blocks
    of _CHUNK_STEPS steps keep the same buffer reuse with compile time
    bounded at two programs (full chunk + remainder) for any epoch length.
    """
    opt = sgd(lr, momentum=0.9)

    def step_body(stacked, opt_state, batch, anchor, mu, use_prox):
        def total_loss(ps):
            losses, metrics = cohort_loss_fn(ps, batch)
            if use_prox:
                prox = sum(
                    jnp.sum(
                        jnp.square(
                            l.astype(jnp.float32) - a.astype(jnp.float32)
                        ),
                        axis=tuple(range(1, l.ndim)),
                    )
                    for l, a in zip(jax.tree.leaves(ps), jax.tree.leaves(anchor))
                )
                losses = losses + 0.5 * mu * prox
            return jnp.sum(losses), metrics

        (_, metrics), grads = jax.value_and_grad(total_loss, has_aux=True)(stacked)
        grads, _ = clip_by_global_norm_stacked(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, stacked, jnp.int32(0))
        return apply_updates(stacked, updates), opt_state, metrics

    def _gather_anchor(table, aidx):
        return jax.tree.map(lambda l: jnp.take(l, aidx, axis=0), tree_stack(table))

    @functools.partial(jax.jit, static_argnames=("use_prox", "steps"))
    def fit_fused(table, aidx, batches, mu, use_prox, steps):
        anchor = _gather_anchor(table, aidx)
        stacked = anchor
        opt_state = opt.init(stacked)
        metrics = {}
        for s in range(steps):
            batch = jax.tree.map(lambda l: l[:, s], batches)
            stacked, opt_state, metrics = step_body(
                stacked, opt_state, batch, anchor, mu, use_prox
            )
        delta = jax.tree.map(jnp.subtract, stacked, anchor)
        return delta, metrics

    @functools.partial(
        jax.jit, static_argnames=("use_prox", "chunk"), donate_argnums=(0, 1)
    )
    def run_chunk(stacked, opt_state, batches, anchor, mu, use_prox, chunk):
        metrics = {}
        for s in range(chunk):
            batch = jax.tree.map(lambda l: l[:, s], batches)
            stacked, opt_state, metrics = step_body(
                stacked, opt_state, batch, anchor, mu, use_prox
            )
        return stacked, opt_state, metrics

    @jax.jit
    def init_state(table, aidx):
        # materialize the gathered [R, ...] anchor once: the chunk loop
        # donates its carry, the anchor must survive for the prox term and
        # the final delta
        anchor = _gather_anchor(table, aidx)
        return jax.tree.map(jnp.copy, anchor), opt.init(anchor), anchor

    @jax.jit
    def finalize(stacked, anchor):
        return jax.tree.map(jnp.subtract, stacked, anchor)

    def run_rows(table, aidx, batches, mu, use_prox):
        # table: tuple of PLANE_ROWS params trees; aidx: [R] row->table
        # gather index; batches: leaves [R, steps, ...]
        leaves = jax.tree.leaves(batches)
        r, steps = leaves[0].shape[:2]
        run_rows.dispatch_widths.append(int(r))
        if steps <= _UNROLL_LIMIT:
            return fit_fused(table, aidx, batches, mu, use_prox, steps)
        stacked, opt_state, anchor = init_state(table, aidx)
        metrics = {}
        s = 0
        while s < steps:
            chunk = min(_CHUNK_STEPS, steps - s)
            block = jax.tree.map(lambda l: l[:, s : s + chunk], batches)
            stacked, opt_state, metrics = run_chunk(
                stacked, opt_state, block, anchor, mu, use_prox, chunk
            )
            s += chunk
        return finalize(stacked, anchor), metrics

    run_rows.dispatch_widths = []
    return run_rows


def _unstack_metrics(stacked: Dict[str, Any], n: int) -> List[Dict[str, float]]:
    host = to_host(stacked, "fit_metrics")
    return [{k: float(v[i]) for k, v in host.items()} for i in range(n)]


def _row_blocks(anchors: Sequence[Any], anchor_idx, rows: Sequence[Any],
                mus: Sequence[float]):
    """Split a plane into PLANE_ROWS-row blocks.

    Yields (anchor table, anchor_idx, rows, mus) per block. A block's table
    holds only the distinct anchors its rows reference, in first-use order;
    the index, rows and mus are padded to PLANE_ROWS by repeating their
    first entry, and padding rows' results are discarded.
    ``anchor_idx=None`` means anchors is per-row (identity mapping).
    """
    aidx = list(range(len(rows)) if anchor_idx is None else anchor_idx)
    for s in range(0, len(rows), PLANE_ROWS):
        local: Dict[int, int] = {}
        for a in aidx[s : s + PLANE_ROWS]:
            local.setdefault(a, len(local))
        idx = [local[a] for a in aidx[s : s + PLANE_ROWS]]
        blk_rows = list(rows[s : s + PLANE_ROWS])
        blk_mus = [float(m) for m in mus[s : s + PLANE_ROWS]]
        pad = PLANE_ROWS - len(blk_rows)
        yield (
            [anchors[a] for a in local],
            idx + [idx[0]] * pad,
            blk_rows + [blk_rows[0]] * pad,
            blk_mus + [blk_mus[0]] * pad,
        )


def _fit_blocks(runner, batches_for, anchors, rows, steps, mus, use_prox, anchor_idx):
    """Run a plane block by block through ``runner``; ``batches_for(rows)``
    builds one block's step batches on the host (numpy leaves
    [PLANE_ROWS, steps, ...]), copied to the device here. The anchor table
    goes to the program as PLANE_ROWS references (padded by repeating the
    first anchor, so the program's inputs keep one shape) and the index and
    mus as numpy arrays: the program stacks and gathers them. Returns
    (plane [bucket_rows(R), ...], per-row last-step metrics [R])."""
    planes, lasts = [], []
    for table, idx, blk_rows, blk_mus in _row_blocks(anchors, anchor_idx, rows, mus):
        with span("fit.batches", rows=len(blk_rows), steps=steps):
            host = batches_for(blk_rows)
        with span("fit.h2d") as s:
            batches = {k: jnp.asarray(v) for k, v in host.items()}
            s.set_metadata(bytes=sum(b.nbytes for b in batches.values()))
        with span("fit.anchors", anchors=len(table)):
            table = tuple(table + [table[0]] * (PLANE_ROWS - len(table)))
            aidx = np.asarray(idx, np.int32)
            mu = np.asarray(blk_mus, np.float32)
        with span("fit.dispatch", rows=len(blk_rows), steps=steps):
            plane, last = runner(table, aidx, batches, mu, use_prox)
        planes.append(plane)
        lasts.append(last)

    def cat(*ls):
        return ls[0] if len(ls) == 1 else jnp.concatenate(ls)

    return (
        jax.tree.map(cat, *planes),
        _unstack_metrics(jax.tree.map(cat, *lasts), len(rows)),
    )


def _sgd_local_fit(loss_fn, lr: float, batch_size: int):
    opt = sgd(lr, momentum=0.9)

    @jax.jit
    def step(params, opt_state, batch, anchor, mu):
        def full_loss(p):
            l, metrics = loss_fn(p, batch)
            if mu is not None:
                prox = sum(
                    jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
                    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(anchor))
                )
                l = l + 0.5 * mu * prox
            return l, metrics

        (loss, metrics), grads = jax.value_and_grad(full_loss, has_aux=True)(params)
        grads, _ = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, params, jnp.int32(0))
        return apply_updates(params, updates), opt_state, metrics

    def fit(params, client: "EdgeClient", steps: int, rng: np.random.Generator, prox_mu: float):
        anchor = params
        opt_state = opt.init(params)
        metrics = {}
        n_used = 0
        it = client.dataset.batches(batch_size, rng=rng, epochs=1000)
        for _ in range(steps):
            batch = next(it)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, metrics = step(
                params, opt_state, batch, anchor, prox_mu if prox_mu > 0 else None
            )
            n_used += batch_size
        delta = tree_sub(params, anchor)
        return delta, n_used, {k: float(v) for k, v in to_host(metrics, "fit_metrics").items()}

    return fit


def _sgd_plane_fns(cohort_loss_fn, lr: float, batch_size: int):
    """MNIST-style plane fns: batch plans are index arrays into the
    client's shard; rows gather their step batches from dataset arrays."""
    runner = _plane_sgd_runner(cohort_loss_fn, lr)

    def plan_fit(clients: List["EdgeClient"], steps: int, rng: np.random.Generator):
        # plans drawn per client IN ORDER: same rng stream as the
        # sequential path pulling `steps` batches per client.
        return [c.dataset.batch_indices(batch_size, steps, rng=rng) for c in clients]

    def plan_digest(client: "EdgeClient", plan: np.ndarray):
        return (id(client.dataset), plan.tobytes())

    def batches_for(rows):
        return {
            "images": np.stack([c.dataset.images[p] for c, p in rows]),
            "labels": np.stack([c.dataset.labels[p] for c, p in rows]),
        }

    def fit_rows(anchors, rows, steps, mus, use_prox, anchor_idx=None):
        plane, metrics = _fit_blocks(
            runner, batches_for, anchors, rows, steps, mus, use_prox, anchor_idx
        )
        return plane, [steps * batch_size] * len(rows), metrics

    fit_rows.runner = runner
    return plan_fit, plan_digest, fit_rows


def _plane_batched_local_fit(plan_fit, fit_rows):
    """Default cohort-batched fit on top of the plane API: every row shares
    the cohort's single anchor (gathered per row inside the fit program);
    the plane is sliced back to cohort width."""

    def fit_cohort(
        params,
        clients: List["EdgeClient"],
        steps: int,
        rng: np.random.Generator,
        prox_mu: float,
    ):
        with span("plan", rows=len(clients)):
            plans = plan_fit(clients, steps, rng)
        rows = list(zip(clients, plans))
        plane, n_examples, metrics = fit_rows(
            [params], rows, steps, [prox_mu] * len(rows), prox_mu > 0,
            anchor_idx=[0] * len(rows),
        )
        stacked = jax.tree.map(lambda l: l[: len(rows)], plane)
        return stacked, n_examples, metrics

    return fit_cohort


def mnist_cnn_task(lr: float = 0.05, batch_size: int = 32) -> LocalTask:
    """The paper's workload: MNIST CNN, ~1.6 MB params -> ~3.2 MB update
    (float32 down+up per round ~= the paper's 3 MB/round/10-client figure)."""
    params_t = cnn_init(jax.random.PRNGKey(0))
    nbytes = sum(int(np.prod(p.shape)) * 4 for p in jax.tree.leaves(params_t))

    @jax.jit
    def ev(params, images, labels):
        logits = cnn_apply(params, images)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
        return acc, nll

    def evaluate(params, data: Dict[str, np.ndarray]):
        with span("evaluate", examples=len(data["labels"])):
            acc, nll = to_host(
                ev(params, jnp.asarray(data["images"]), jnp.asarray(data["labels"])), "eval"
            )
        return {"accuracy": float(acc), "loss": float(nll)}

    plan_fit, plan_digest, fit_rows = _sgd_plane_fns(cnn_loss_stacked, lr, batch_size)
    return LocalTask(
        "mnist_cnn",
        init_fn=cnn_init,
        local_fit=_sgd_local_fit(cnn_loss, lr, batch_size),
        evaluate=evaluate,
        update_bytes=nbytes,
        batched_local_fit=_plane_batched_local_fit(plan_fit, fit_rows),
        plan_fit=plan_fit,
        plan_digest=plan_digest,
        fit_rows=fit_rows,
    )


def lm_task(cfg, lr: float = 1e-3, batch_size: int = 4, seq: int = 64) -> LocalTask:
    """Reduced-LM payload: any arch config can be the FL workload."""
    from repro.data.tokens import token_batch_for
    from repro.models import Model

    model = Model(cfg)

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def fit(params, client, steps, rng, prox_mu):
        # token shards: synthesize per-client batches (dataset carries id)
        anchor = params
        from repro.optim import sgd as _sgd

        opt = _sgd(lr, momentum=0.9)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state, batch):
            (l, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            grads, _ = clip_by_global_norm(grads, 1.0)
            updates, opt_state = opt.update(grads, opt_state, params, jnp.int32(0))
            return apply_updates(params, updates), opt_state, metrics

        metrics = {}
        for s in range(steps):
            batch = token_batch_for(
                cfg, batch=batch_size, seq=seq,
                seed=int(rng.integers(0, 2**31)), client_id=client.client_id,
            )
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, metrics = step(params, opt_state, batch)
        return tree_sub(params, anchor), steps * batch_size, {
            k: float(v) for k, v in to_host(metrics, "fit_metrics").items()
        }

    def evaluate(params, data):
        with span("evaluate", examples=batch_size):
            batch = token_batch_for(cfg, batch=batch_size, seq=seq, seed=7, client_id=10_000)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            loss, metrics = jax.jit(loss_fn)(params, batch)
            metrics = to_host(metrics, "eval")
        return {k: float(v) for k, v in metrics.items()}

    def cohort_loss(ps, batch):
        # LM losses are matmul-dominated, so a plain vmap (one step, no
        # scan) lowers to batched GEMMs and stays fast.
        losses, metrics = jax.vmap(loss_fn)(ps, batch)
        return losses, metrics

    runner = _plane_sgd_runner(cohort_loss, lr)

    def plan_fit(clients, steps, rng):
        # same seed draws, same order as the sequential fit loop
        return [
            [int(rng.integers(0, 2**31)) for _ in range(steps)] for _ in clients
        ]

    def plan_digest(client, plan):
        return (client.client_id, tuple(plan))

    def batches_for(rows):
        per_row = []
        for c, plan in rows:
            bs = [
                token_batch_for(
                    cfg, batch=batch_size, seq=seq, seed=s, client_id=c.client_id
                )
                for s in plan
            ]
            per_row.append({k: np.stack([b[k] for b in bs]) for k in bs[0]})
        return {k: np.stack([pr[k] for pr in per_row]) for k in per_row[0]}

    def fit_rows(anchors, rows, steps, mus, use_prox, anchor_idx=None):
        plane, metrics = _fit_blocks(
            runner, batches_for, anchors, rows, steps, mus, use_prox, anchor_idx
        )
        return plane, [steps * batch_size] * len(rows), metrics

    fit_rows.runner = runner

    params_t = model.abstract_params()
    nbytes = sum(int(np.prod(p.shape)) * 4 for p in jax.tree.leaves(params_t))
    return LocalTask(
        f"lm_{cfg.name}", model.init, fit, evaluate, nbytes,
        batched_local_fit=_plane_batched_local_fit(plan_fit, fit_rows),
        plan_fit=plan_fit,
        plan_digest=plan_digest,
        fit_rows=fit_rows,
    )


@dataclass
class EdgeClient:
    client_id: int
    dataset: Optional[ClientDataset] = None
    compute_rate: float = 1.0  # 1.0 = the paper's 0.5 vCPU Pi-class baseline
    link_override: Optional[Any] = None  # LinkProfile or None (use base)
    connected: bool = False
    residual: Optional[Any] = None  # compression error feedback
    rounds_participated: int = 0
    bytes_sent: int = 0

    def step_time(self, base_step_cost: float) -> float:
        return base_step_cost / max(self.compute_rate, 1e-6)
