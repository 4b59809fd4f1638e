"""Plain reference for the FL configurations: the MNIST CNN trained by
FedAvg, written from the paper's description in straightforward
``jax.numpy`` and imported from nothing in the program.

One FL round of a point, as the configurations state it:

- every delivering client starts from the global params and runs
  ``steps`` SGD steps (lr, momentum, gradient clipped to global norm
  ``clip_norm``) on its batch plan; its update is its params minus the
  start;
- the server adds the mean of the updates weighted by examples trained;
- the server's eval loss is the mean negative log-likelihood on the eval set.

The draws that pick each round's cohort and each client's batches follow
the engine's published stream discipline (``derive_rng``: one numpy
``SeedSequence(seed, spawn_key=(stream, round))`` stream per round under
split streams; one ``default_rng(seed)`` stream otherwise) and are
replayed here with numpy alone.

Computed in float32 at ``highest`` matmul precision; ``dtype=bfloat16``
gives the control: the same reference one precision below.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops

COHORT_STREAM = 1  # the selection-then-plans stream tag under split streams
BLOCK = 8  # clients per reference program (padded; one compile per shape)


@jax.jit
def init_from_key(key):
    """He-normal init of the CNN from one key, on the device: the weights
    both the program and the reference start from."""
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def he(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * jnp.sqrt(2.0 / fan_in)

    return {
        "conv1": {"w": he(k1, (3, 3, 1, 16), 9), "b": jnp.zeros((16,))},
        "conv2": {"w": he(k2, (3, 3, 16, 32), 144), "b": jnp.zeros((32,))},
        "fc1": {"w": he(k3, (32 * 7 * 7, 128), 32 * 49), "b": jnp.zeros((128,))},
        "fc2": {"w": he(k4, (128, 10), 128), "b": jnp.zeros((10,))},
    }


def init_params(seed: int):
    return init_from_key(jax.random.PRNGKey(seed))


def flops_per_example(cfg) -> Dict[str, float]:
    """FLOPs of one example: in an SGD step (forward and backward) and in
    an eval forward pass."""
    return {"train": flops.TRAIN_FACTOR * flops.CNN_FORWARD_FLOPS, "eval": flops.CNN_FORWARD_FLOPS}


def forward(p, images):
    """[B, 28, 28, 1] -> logits [B, 10]: 2 x (conv3x3 SAME + relu +
    maxpool 2x2) -> dense 128 + relu -> dense 10."""

    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + b)

    def pool(x):
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = pool(conv(images, p["conv1"]["w"], p["conv1"]["b"]))
    x = pool(conv(x, p["conv2"]["w"], p["conv2"]["b"]))
    x = jax.nn.relu(x.reshape(x.shape[0], -1) @ p["fc1"]["w"] + p["fc1"]["b"])
    return x @ p["fc2"]["w"] + p["fc2"]["b"]


def nll(p, images, labels):
    logp = jax.nn.log_softmax(forward(p, images))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _client_update(p0, images, labels, lr, momentum, clip):
    """One client's local epoch: images [steps, B, ...] -> its update."""
    p = p0
    m = jax.tree.map(jnp.zeros_like, p0)
    for s in range(images.shape[0]):
        g = jax.grad(nll)(p, images[s], labels[s])
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                          for l in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
        g = jax.tree.map(lambda l: l * scale.astype(l.dtype), g)
        m = jax.tree.map(lambda a, b: momentum * a + b, m, g)
        p = jax.tree.map(lambda a, b: a - lr * b, p, m)
    return jax.tree.map(jnp.subtract, p, p0)


@functools.partial(jax.jit, static_argnames=("lr", "momentum", "clip"))
def _block_sum(p0, images, labels, w, lr, momentum, clip):
    """Sum over a block of clients of weight x update (weights 0 on pads)."""
    ups = jax.vmap(_client_update, in_axes=(None, 0, 0, None, None, None))(
        p0, images, labels, lr, momentum, clip)
    return jax.tree.map(lambda u: jnp.einsum("c,c...->...", w.astype(u.dtype), u), ups)


_eval_loss = jax.jit(nll)


def _cast(tree, dtype):
    return jax.tree.map(lambda l: l.astype(dtype), tree)


def fedavg_round(p, shards: Sequence, plans: Sequence[np.ndarray], *, lr: float,
                 momentum: float, clip: float, dtype=jnp.float32):
    """New global params after one FedAvg round over the delivering clients
    ``shards`` [{"images", "labels"}] with batch plans [steps, B] each."""
    n = len(shards)
    weights = np.array([plan.size for plan in plans], np.float64)
    weights = weights / weights.sum()
    total = None
    for s in range(0, n, BLOCK):
        idx = list(range(s, min(s + BLOCK, n)))
        pad = BLOCK - len(idx)
        imgs = np.stack([shards[i]["images"][plans[i]] for i in idx]
                        + [shards[idx[0]]["images"][plans[idx[0]]]] * pad)
        labs = np.stack([shards[i]["labels"][plans[i]] for i in idx]
                        + [shards[idx[0]]["labels"][plans[idx[0]]]] * pad)
        w = np.concatenate([weights[idx], np.zeros(pad)]).astype(np.float32)
        part = _block_sum(p, jnp.asarray(imgs, dtype), jnp.asarray(labs), jnp.asarray(w),
                          lr=lr, momentum=momentum, clip=clip)
        total = part if total is None else jax.tree.map(jnp.add, total, part)
    return jax.tree.map(jnp.add, p, total)


def eval_loss(p, eval_data: Dict[str, np.ndarray], dtype=jnp.float32) -> float:
    return float(_eval_loss(p, jnp.asarray(eval_data["images"], dtype),
                            jnp.asarray(eval_data["labels"])))


def batch_plan(rng: np.random.Generator, n: int, batch: int, steps: int) -> np.ndarray:
    """[steps, batch] example indices: one permutation per epoch entered,
    consecutive batch-sized slices, the last partial batch dropped."""
    out: List[np.ndarray] = []
    while len(out) < steps:
        order = rng.permutation(n)
        for i in range(0, n - batch + 1, batch):
            out.append(order[i: i + batch])
            if len(out) == steps:
                break
    return np.stack(out)


def round_stream(seed: int, split: bool, rnd: int, single=None):
    """The generator round ``rnd``'s cohort and plan draws come from: its
    own under split streams, else the one stream ``single`` carries on."""
    if not split:
        return single
    return np.random.default_rng(
        np.random.SeedSequence(entropy=seed, spawn_key=(COHORT_STREAM, rnd)))


def replay(point, rounds: Sequence[int], *, lr: float, momentum: float, clip: float,
           dtype=jnp.float32, start=None):
    """Follow a point through ``rounds``, consecutive round numbers.

    ``point`` gives ``seed``, ``split`` (stream discipline), ``n_live`` and
    ``k`` (cohort draw), ``analytic_draws`` (the analytic transport's one
    uniform per cohort member on the single stream), ``batch``, ``steps``,
    ``shard(cid) -> {"images", "labels"}``, ``eval_data``, ``compressor``
    (only "none" is modelled) and, per round, ``delivered[r]``: the
    delivering client ids in delivery order, None for a failed round.

    The first round starts from ``start`` (the params before it) or, by
    default, from the weights made from the seed; under a single stream
    only a replay from round 0 can follow the draws.

    Returns per round: the replayed cohort ids, the params after the round
    (in ``dtype``), and the eval loss.
    """
    if point["compressor"] != "none":
        raise ValueError(f"no reference for compressor {point['compressor']!r}")
    rounds = list(rounds)
    if not point["split"] and rounds[0] != 0:
        raise ValueError("a single stream is replayed from round 0 only")
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        p = _cast(init_params(point["seed"]) if start is None else start, dtype)
        single = None if point["split"] else np.random.default_rng(point["seed"])
        out = []
        for r in rounds:
            rng = round_stream(point["seed"], point["split"], r, single)
            cohort = rng.choice(point["n_live"], size=point["k"], replace=False)
            if point["analytic_draws"]:
                rng.random(point["k"])
            ids = point["delivered"][r]
            if ids is None:  # a failed round: no update, no eval
                out.append({"cohort": [int(c) for c in cohort], "params": p, "loss": None})
                continue
            shards = [point["shard"](c) for c in ids]
            plans = [batch_plan(rng, len(s["labels"]), point["batch"], point["steps"]) for s in shards]
            p = fedavg_round(p, shards, plans, lr=lr, momentum=momentum, clip=clip, dtype=dtype)
            out.append({"cohort": [int(c) for c in cohort], "params": p,
                        "loss": eval_loss(p, point["eval_data"], dtype)})
        return out
