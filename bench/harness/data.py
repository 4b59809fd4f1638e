"""The benchmark's own data, made from the seed and named by the
configuration's ``"data"`` key:

- ``"synthetic_mnist"``: labelled images, partitioned by the
  configuration's ``partition``;
- ``"tokens"``: Markov token sequences of ``seq_len + 1`` ids over
  ``vocab_size``.

Copies of the generators in ``repro.data.federated`` (``synthetic_mnist``,
``iid_partition``, ``federated_mnist_factory``) and ``repro.data.tokens``
(``_markov_tokens``), kept here so that no change to the program can
change the benchmark's inputs. A shard or eval set is a dict of arrays by
field name; the program receives each shard wrapped in its own
``ClientDataset`` input type (``dataset``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import numpy as np

PROTO_SEED = 1234

Arrays = Dict[str, np.ndarray]


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


def synthetic_mnist(n: int, seed) -> Arrays:
    """``n`` labelled examples: images [n, 28, 28, 1] f32, labels [n] i32."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    return {"images": _images(rng, labels, prototypes()), "labels": labels}


def iid_shards(n_clients: int, per_client: int, seed):
    """IID partition of one synthetic pool: a list of shards."""
    data = synthetic_mnist(n_clients * per_client, seed)
    order = np.random.default_rng(seed).permutation(n_clients * per_client)
    return [{k: v[idx] for k, v in data.items()} for idx in np.array_split(order, n_clients)]


def client_shard(seed, client_id: int, n: int, alpha: float, protos: np.ndarray) -> Arrays:
    """Client ``client_id``'s own shard of ``n`` examples, drawn from its
    ``SeedSequence((seed, client_id))`` stream with Dirichlet(alpha) label
    skew, or uniform labels where ``alpha`` is None: O(n) work, no
    population-wide pool."""
    rng = _client_rng(seed, client_id)
    if alpha is None:
        labels = rng.integers(0, 10, size=n).astype(np.int32)
    else:
        props = rng.dirichlet([alpha] * 10)
        labels = rng.choice(10, size=n, p=props).astype(np.int32)
    return {"images": _images(rng, labels, protos), "labels": labels}


def _client_rng(seed, client_id: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(entropy=seed, spawn_key=(int(client_id),)))


def markov_tokens(rng: np.random.Generator, n: int, vocab: int, order_bias: float = 0.85):
    """Tokens where t_{i+1} is usually (t_i * 7 + 3) % vocab -- learnable."""
    toks = np.empty(n, dtype=np.int32)
    toks[0] = rng.integers(0, vocab)
    jumps = rng.random(n) > order_bias
    rand = rng.integers(0, vocab, size=n)
    for i in range(1, n):
        toks[i] = rand[i] if jumps[i] else (toks[i - 1] * 7 + 3) % vocab
    return toks


def token_sequences(rng: np.random.Generator, n: int, seq_len: int, vocab: int) -> Arrays:
    """``n`` sequences of ``seq_len + 1`` ids: tokens [n, seq_len + 1] i32."""
    return {"tokens": markov_tokens(rng, n * (seq_len + 1), vocab).reshape(n, seq_len + 1)}


Source = Tuple[Callable[[int], Arrays], Arrays]


def _mnist(cfg: Dict[str, Any], seed: int) -> Source:
    """Shards by the configuration's partition; the eval set from (seed, 1)."""
    n = cfg["examples_per_client"]
    if cfg["partition"] == "iid" and "n_clients" in cfg:
        pool = iid_shards(cfg["n_clients"], n, [seed, 0])
        shard = pool.__getitem__
    elif cfg["partition"] in ("iid", "dirichlet"):
        alpha = cfg["dirichlet_alpha"] if cfg["partition"] == "dirichlet" else None
        protos = prototypes()

        def shard(c):
            return client_shard([seed, 2], c, n, alpha, protos)
    else:
        raise ValueError(f"unknown partition {cfg['partition']!r}")
    return shard, synthetic_mnist(cfg["eval_examples"], [seed, 1])


def _tokens(cfg: Dict[str, Any], seed: int) -> Source:
    """Each client's ``examples_per_client`` sequences from its
    ``SeedSequence((seed, 2), client)`` stream; the eval set's
    ``eval_examples`` from (seed, 1)."""
    n, seq_len, vocab = cfg["examples_per_client"], cfg["seq_len"], cfg["vocab_size"]

    def shard(c):
        return token_sequences(_client_rng([seed, 2], c), n, seq_len, vocab)

    return shard, token_sequences(np.random.default_rng([seed, 1]), cfg["eval_examples"],
                                  seq_len, vocab)


SOURCES = {"synthetic_mnist": _mnist, "tokens": _tokens}


def source(cfg: Dict[str, Any], seed: int) -> Source:
    """The configuration's data: client id -> shard, and the eval set."""
    if cfg.get("data") not in SOURCES:
        raise ValueError(f"unknown data {cfg.get('data')!r}; known: {sorted(SOURCES)}")
    return SOURCES[cfg["data"]](cfg, int(seed))


def examples(arrays: Arrays) -> int:
    """The number of examples: the leading axis of the data's arrays."""
    return int(next(iter(arrays.values())).shape[0])


def dataset(client_id: int, arrays: Arrays):
    """``arrays`` wrapped by field name in the program's ``ClientDataset``."""
    return _dataset_type(tuple(arrays))(int(client_id), **arrays)


@functools.lru_cache(maxsize=None)
def _dataset_type(names: Tuple[str, ...]):
    """The program's ``ClientDataset`` where it has a field for each of
    ``names``; else a subclass of it that adds the missing fields, every
    data field optional, and counts examples by the leading axis of the
    fields set."""
    from repro.data import ClientDataset

    own = [f.name for f in dataclasses.fields(ClientDataset) if f.name != "client_id"]
    if set(names) <= set(own):
        return ClientDataset
    fields = [(n, Any, dataclasses.field(default=None))
              for n in own + [n for n in names if n not in own]]

    def num_examples(self) -> int:
        return examples({n: getattr(self, n) for n in names})

    return dataclasses.make_dataclass("ClientDataset", fields, bases=(ClientDataset,),
                                      namespace={"num_examples": num_examples})
