"""FL aggregation strategies (Flower's Strategy abstraction, rebuilt).

All strategies speak *deltas*: clients send (new_params - global_params);
the server turns the aggregated delta into the next global model. FedAvg is
the paper's baseline; FedProx/FedOpt/robust variants are the "advanced
reliability techniques" tier the paper's Table III points practitioners to.

``min_fit_fraction`` / ``min_eval_fraction`` implement Flower's
min_fit_clients semantics — the paper's Recommendation #3 knob.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.optim import Optimizer, fedopt_server, nesterov_outer
from repro.utils.spans import span
from repro.utils import (
    tree_add,
    tree_scale,
    tree_unstack,
    tree_weighted_mean,
    tree_zeros_like,
)


@dataclass
class Strategy:
    name: str
    min_fit_fraction: float = 0.5  # Flower default-ish; paper tunes to 0.1
    min_eval_fraction: float = 0.5
    prox_mu: float = 0.0  # >0 => FedProx client regularizer
    server_opt: Optional[Optimizer] = None
    server_state: Optional[dict] = None
    aggregate_fn: Callable = None  # (deltas, weights) -> delta
    # Stacked twin of aggregate_fn for the batched cohort engine:
    # (stacked_deltas [C,...], weights [C]) -> delta. None => the server
    # unstacks and falls back to the list path.
    stacked_aggregate_fn: Callable = None
    # Hashable identity of the AGGREGATION semantics (not the quorum
    # knobs): two strategies with equal fingerprints map equal (deltas,
    # weights, step) to equal new params. The grid engine keys parameter
    # provenance on this to coalesce sweep points that share a trajectory;
    # an empty fingerprint disables sharing for that strategy.
    agg_fingerprint: tuple = ()
    # True for order-statistic aggregators (trimmed_mean/median/krum) whose
    # semantics degenerate on a single update. The async engine's
    # buffer-flush aggregation refuses async_buffer_k < 2 for these —
    # aggregating a buffer of one would silently reduce them to identity.
    robust: bool = False

    def quorum(self, n_total: int) -> int:
        return max(1, int(np.ceil(self.min_fit_fraction * n_total)))

    def aggregate(self, global_params, deltas: Sequence, weights: Sequence[float], step: int):
        """Returns new global params given delivered client deltas."""
        return self._apply(global_params, self.aggregate_fn(deltas, weights), step)

    def aggregate_stacked(self, global_params, stacked_deltas, weights, step: int):
        """Batched-engine entry: deltas arrive stacked along a leading client
        axis; the weighted-mean family reduces them in one kernel pass with
        no per-client scaled copies."""
        with span("aggregate", rows=len(weights)):
            if self.stacked_aggregate_fn is None:
                return self.aggregate(global_params, tree_unstack(stacked_deltas), weights, step)
            agg = self.stacked_aggregate_fn(stacked_deltas, weights)
            return self._apply(global_params, agg, step)

    def _apply(self, global_params, agg_delta, step: int):
        if self.server_opt is None:
            return tree_add(global_params, agg_delta)
        if self.server_state is None:
            self.server_state = self.server_opt.init(global_params)
        upd, self.server_state = self.server_opt.update(
            agg_delta, self.server_state, global_params, jnp.int32(step)
        )
        return tree_add(global_params, upd)


def _weighted_mean(deltas, weights):
    return tree_weighted_mean(list(deltas), np.asarray(weights, np.float64))


@functools.partial(jax.jit, static_argnames=())
def _stacked_mean_xla(stacked, w):
    """One-pass stacked weighted mean (the kernel's oracle semantics)."""
    wn = w / jnp.maximum(jnp.sum(w), 1e-20)

    def one(leaf):
        c = leaf.shape[0]
        flat = leaf.astype(jnp.float32).reshape(c, -1)
        out = jnp.einsum("c,cn->n", wn, flat)
        return out.reshape(leaf.shape[1:]).astype(leaf.dtype)

    return jax.tree.map(one, stacked)


def _weighted_mean_stacked(stacked, weights):
    """Kernel-backed FedAvg reduction over stacked deltas [C, ...].

    On TPU this routes through the compiled Pallas ``fedavg_reduce`` kernel
    (one streamed pass, f32 accumulator, no per-client scaled copies). Off
    TPU the kernel only exists in interpret mode — several times slower
    than XLA — so the same one-pass reduction runs as a stacked einsum with
    identical normalization semantics (tests assert kernel == oracle in
    interpret mode; the server hot path stays fast on CPU CI).
    """
    w = jnp.asarray(np.asarray(weights), jnp.float32)
    if kernel_ops.default_interpret():
        return _stacked_mean_xla(stacked, w)
    return kernel_ops.fedavg_reduce(stacked, w, interpret=False)


def fedavg(min_fit: float = 0.5, min_eval: float = 0.5) -> Strategy:
    """McMahan et al. FedAvg — the paper's configuration."""
    return Strategy(
        "fedavg", min_fit, min_eval,
        aggregate_fn=_weighted_mean, stacked_aggregate_fn=_weighted_mean_stacked,
        agg_fingerprint=("wmean",),
    )


def fedprox(mu: float = 0.01, min_fit: float = 0.5) -> Strategy:
    return Strategy(
        "fedprox", min_fit, min_fit, prox_mu=mu,
        aggregate_fn=_weighted_mean, stacked_aggregate_fn=_weighted_mean_stacked,
        agg_fingerprint=("wmean",),
    )


def fedopt(kind: str = "adam", server_lr: float = 0.1, min_fit: float = 0.5) -> Strategy:
    return Strategy(
        f"fed{kind}",
        min_fit,
        min_fit,
        server_opt=fedopt_server(kind, lr=server_lr),
        aggregate_fn=_weighted_mean,
        stacked_aggregate_fn=_weighted_mean_stacked,
        agg_fingerprint=("wmean", "fedopt", kind, float(server_lr)),
    )


def diloco(outer_lr: float = 0.7, outer_momentum: float = 0.9, min_fit: float = 0.5) -> Strategy:
    """Local-SGD outer Nesterov — the cross-pod datacenter configuration."""
    return Strategy(
        "diloco",
        min_fit,
        min_fit,
        server_opt=nesterov_outer(outer_lr, outer_momentum),
        aggregate_fn=_weighted_mean,
        stacked_aggregate_fn=_weighted_mean_stacked,
        agg_fingerprint=("wmean", "nesterov", float(outer_lr), float(outer_momentum)),
    )


def trimmed_mean(trim_fraction: float = 0.1, min_fit: float = 0.5) -> Strategy:
    """Coordinate-wise trimmed mean (robust to corrupt/straggled updates)."""

    def _trim_one(x, k):
        xs = jnp.sort(x.astype(jnp.float32), axis=0)
        xs = xs[k : xs.shape[0] - k] if xs.shape[0] > 2 * k else xs
        return jnp.mean(xs, axis=0).astype(x.dtype)

    def agg(deltas, weights):
        deltas = list(deltas)
        k = int(len(deltas) * trim_fraction)
        return jax.tree.map(
            lambda *leaves: _trim_one(jnp.stack(leaves), k), *deltas
        )

    def agg_stacked(stacked, weights):
        c = jax.tree.leaves(stacked)[0].shape[0]
        k = int(c * trim_fraction)
        return jax.tree.map(lambda x: _trim_one(x, k), stacked)

    return Strategy(
        "trimmed_mean", min_fit, min_fit,
        aggregate_fn=agg, stacked_aggregate_fn=agg_stacked,
        agg_fingerprint=("trimmed_mean", float(trim_fraction)),
        robust=True,
    )


def median(min_fit: float = 0.5) -> Strategy:
    def _median_one(x):
        return jnp.median(x.astype(jnp.float32), axis=0).astype(x.dtype)

    def agg(deltas, weights):
        return jax.tree.map(
            lambda *leaves: _median_one(jnp.stack(leaves)), *list(deltas)
        )

    def agg_stacked(stacked, weights):
        return jax.tree.map(_median_one, stacked)

    return Strategy(
        "median", min_fit, min_fit,
        aggregate_fn=agg, stacked_aggregate_fn=agg_stacked,
        agg_fingerprint=("median",),
        robust=True,
    )


def krum(n_byzantine: int = 1, min_fit: float = 0.5) -> Strategy:
    """Krum (Blanchard et al.): pick the delta closest to its neighbours."""

    def _krum_pick(V, n):
        d2 = jnp.sum((V[:, None] - V[None, :]) ** 2, axis=-1)
        m = n - n_byzantine - 2
        scores = jnp.sum(jnp.sort(d2, axis=1)[:, 1 : m + 1], axis=1)
        return int(jnp.argmin(scores))

    def agg(deltas, weights):
        deltas = list(deltas)
        n = len(deltas)
        if n <= 2 * n_byzantine + 2:
            return _weighted_mean(deltas, weights)
        vecs = [
            jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in jax.tree.leaves(d)])
            for d in deltas
        ]
        return deltas[_krum_pick(jnp.stack(vecs), n)]

    def agg_stacked(stacked, weights):
        leaves = jax.tree.leaves(stacked)
        n = leaves[0].shape[0]
        if n <= 2 * n_byzantine + 2:
            return _weighted_mean_stacked(stacked, weights)
        V = jnp.concatenate(
            [l.astype(jnp.float32).reshape(n, -1) for l in leaves], axis=1
        )
        best = _krum_pick(V, n)
        return jax.tree.map(lambda l: l[best], stacked)

    return Strategy(
        "krum", min_fit, min_fit,
        aggregate_fn=agg, stacked_aggregate_fn=agg_stacked,
        agg_fingerprint=("krum", int(n_byzantine)),
        robust=True,
    )


STRATEGIES = {
    "fedavg": fedavg,
    "fedprox": fedprox,
    "fedadam": lambda **kw: fedopt("adam", **kw),
    "fedyogi": lambda **kw: fedopt("yogi", **kw),
    "fedadagrad": lambda **kw: fedopt("adagrad", **kw),
    "diloco": diloco,
    "trimmed_mean": trimmed_mean,
    "median": median,
    "krum": krum,
}
