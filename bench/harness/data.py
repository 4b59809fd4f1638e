"""The benchmark's own data: synthetic MNIST shards made from the seed.

A copy of the generators in ``repro.data.federated`` (``synthetic_mnist``,
``iid_partition``, ``federated_mnist_factory``), kept here so that no
change to the program can change the benchmark's inputs. The program
receives the arrays wrapped in its own ``ClientDataset`` input type.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

PROTO_SEED = 1234


def prototypes() -> np.ndarray:
    """10 class prototypes [10, 28, 28]: coarse 7x7 masks upsampled."""
    rng = np.random.default_rng(PROTO_SEED)
    coarse = (rng.random((10, 7, 7)) > 0.55).astype(np.float32)
    return coarse.repeat(4, axis=1).repeat(4, axis=2)


def _images(rng: np.random.Generator, labels: np.ndarray, protos: np.ndarray):
    n = labels.shape[0]
    scale = rng.uniform(0.35, 0.75, (n, 1, 1)).astype(np.float32)
    images = protos[labels] * scale + rng.normal(0, 0.45, (n, 28, 28)).astype(np.float32)
    return np.clip(images, 0.0, 1.0)[..., None].astype(np.float32)


def synthetic_mnist(n: int, seed) -> Dict[str, np.ndarray]:
    """``n`` labelled examples: images [n, 28, 28, 1] f32, labels [n] i32."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    return {"images": _images(rng, labels, prototypes()), "labels": labels}


def iid_shards(n_clients: int, per_client: int, seed):
    """IID partition of one synthetic pool: a list of (images, labels)."""
    data = synthetic_mnist(n_clients * per_client, seed)
    order = np.random.default_rng(seed).permutation(n_clients * per_client)
    return [
        (data["images"][idx], data["labels"][idx])
        for idx in np.array_split(order, n_clients)
    ]


def client_shard(seed, client_id: int, n: int, alpha: float, protos: np.ndarray):
    """Client ``client_id``'s own shard of ``n`` examples, drawn from its
    ``SeedSequence((seed, client_id))`` stream with Dirichlet(alpha) label
    skew, or uniform labels where ``alpha`` is None: O(n) work, no
    population-wide pool."""
    rng = np.random.default_rng(
        np.random.SeedSequence(entropy=seed, spawn_key=(int(client_id),))
    )
    if alpha is None:
        labels = rng.integers(0, 10, size=n).astype(np.int32)
    else:
        props = rng.dirichlet([alpha] * 10)
        labels = rng.choice(10, size=n, p=props).astype(np.int32)
    return _images(rng, labels, protos), labels
