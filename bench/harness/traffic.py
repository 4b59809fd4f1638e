"""The one traffic generator: turns a configuration file and a traffic
file into the engine's inputs, from the seed.

Grid traffic (``"engine": "grid"``) is a paper figure's ladder: one sweep
point per (axis value, TCP preset, replica), the points of sweep k seeded
from ``(seed, k)`` -- spawned one per point, or one shared by every point
-- all on one shard set and one eval set made from the seed. Population
traffic (``"engine": "population"``) is a lazy client universe whose
shards are built from ``(seed, client id)`` when a client is drawn. These
are the program's two entry points, ``run_fl_grid`` and
``FederatedServer.run``.

Everything else is data: links and TCP presets are files of their own
(``bench/links/<name>.json``, ``bench/tcp/<name>.json``) that state every
field the flow reference reads; the data (``harness.data``), payload
task, strategy and matmul precision come from the configuration; a
traffic file's ``server``, ``chaos`` and ``grid`` objects pass through to
``ServerConfig``, ``ChaosSchedule`` events and ``run_fl_grid``.

The ladders and the point factory are copies of ``benchmarks/fig3_latency``,
``benchmarks/fig4_loss`` and ``benchmarks/common`` (``_make_point``,
``spawn_point_seeds``), reduced to data.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

from harness import data

BENCH = Path(__file__).resolve().parents[1]


def spec(kind: str, name: str) -> Dict[str, Any]:
    """A link (``kind="links"``) or TCP preset (``"tcp"``) by name."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def sweep_seeds(seed: int, sweep: int, n: int, how: str) -> List[int]:
    """Point seeds of sweep ``sweep``: ``n`` spawned from ``(seed, sweep)``,
    or the one seed of ``(seed, sweep)`` for every point."""
    ss = np.random.SeedSequence([int(seed), int(sweep)])
    if how == "shared":
        return [int(ss.generate_state(1)[0])] * n
    if how != "spawned":
        raise ValueError(f"unknown point_seeds {how!r}")
    return [int(c.generate_state(1)[0]) for c in ss.spawn(n)]


def leaves(params) -> Dict[str, Any]:
    """Every leaf of a parameter tree by its ``/``-joined path
    (``conv1/w``, ``seg1/mlp/w_gate``)."""
    import jax

    def name(k):
        return str(next(getattr(k, a) for a in ("key", "idx", "name") if hasattr(k, a)))

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {"/".join(name(k) for k in path): leaf for path, leaf in flat}


def check_params(cfg: Dict[str, Any], params, who: str) -> None:
    """``params`` (shapes will do) against the configuration: each path of
    ``layers`` at its shape, the same top-level entries, ``params``
    parameters in all, every leaf in ``dtype``."""
    got = leaves(params)
    errors = []
    for path, shape in cfg["layers"].items():
        if path not in got:
            errors.append(f"no leaf {path}")
        elif list(got[path].shape) != list(shape):
            errors.append(f"{path} is {list(got[path].shape)}, not {list(shape)}")
    top, stated = ({p.split("/")[0] for p in paths} for paths in (got, cfg["layers"]))
    if top != stated:
        errors.append(f"top-level entries {sorted(top)}, not {sorted(stated)}")
    count = sum(int(np.prod(l.shape)) for l in got.values())
    if count != cfg["params"]:
        errors.append(f"{count} parameters, not {cfg['params']}")
    dtypes = sorted({str(l.dtype) for l in got.values()})
    if dtypes != [cfg["dtype"]]:
        errors.append(f"dtypes {dtypes}, not {cfg['dtype']}")
    if errors:
        raise ValueError(f"{cfg['model']} parameters of {who} differ from the configuration's: "
                         + "; ".join(errors))


def make_task(cfg: Dict[str, Any], init_fn):
    """The configuration's payload task (``repro.core.<model>_task``, given
    ``lr``, ``batch_size`` and, where the configuration states it,
    ``seq_len``), starting from the benchmark's weights; the task's and
    the reference's parameters have to be the configuration's."""
    import jax

    import repro.core

    kw = dict(lr=cfg["lr"], batch_size=cfg["batch_size"])
    if "seq_len" in cfg:
        kw["seq_len"] = cfg["seq_len"]
    task = getattr(repro.core, f"{cfg['model']}_task")(**kw)
    key = jax.random.PRNGKey(0)
    check_params(cfg, jax.eval_shape(task.init_fn, key), "the task")
    check_params(cfg, jax.eval_shape(init_fn, key), "the reference")
    return dataclasses.replace(task, init_fn=init_fn)


class _Traffic:
    """What both engines share: data, links, the server's settings."""

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.shard, self.eval_data = data.source(cfg, self.seed)
        self.server_kw = dict(traffic["server"], local_steps=cfg["local_steps"],
                              round_deadline=cfg["round_deadline"],
                              base_step_cost=cfg["base_step_cost"])

    def strategy(self, min_fit: float):
        import repro.core

        return getattr(repro.core, self.cfg["strategy"])(min_fit=min_fit)

    def chaos(self, link: Dict[str, Any]):
        from repro.chaos import ChaosSchedule
        from repro.chaos.schedule import ChaosEvent
        from repro.transport import LinkProfile

        events = [ChaosEvent(**e) for e in self.traffic.get("chaos", [])]
        return ChaosSchedule(LinkProfile(**link), events)

    def flow(self, link: Dict[str, Any], tcp: Dict[str, Any]) -> Dict[str, Any]:
        """What the flow reference needs for one group of flows."""
        nbytes = 4 * self.cfg["params"]
        return {"link": link, "tcp": tcp, "down_bytes": nbytes, "up_bytes": nbytes,
                "idle_s": self.cfg["local_steps"] * self.cfg["base_step_cost"],
                "deadline": self.cfg["round_deadline"]}

    def point(self, seed: int, n_live: int, k: int) -> Dict[str, Any]:
        """What the FL reference needs to follow one point."""
        cfg, srv = self.cfg, self.traffic["server"]
        return {
            "seed": seed, "n_live": n_live, "k": k,
            "split": srv.get("rng_streams") == "split" or srv.get("transport_backend") == "device",
            "analytic_draws": not srv.get("stochastic", False),
            "batch": cfg["batch_size"], "steps": cfg["local_steps"],
            "shard": self.shard, "eval_data": self.eval_data,
            "compressor": self.traffic.get("compressor", "none"),
        }


class GridTraffic(_Traffic):
    """A figure's sweep ladder on the paper's testbed."""

    engine = "grid"

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int):
        super().__init__(cfg, traffic, seed)
        self.datasets = [data.dataset(i, self.shard(i)) for i in range(cfg["n_clients"])]
        base = spec("links", traffic["link"])
        self.specs = [
            (dict(base, **{traffic["axis"]: v, "name": f"{traffic['axis']}{v}"}), spec("tcp", t))
            for v in traffic["values"] for t in traffic["tcp"]
        ] * traffic.get("replicas", 1)
        self.compressor = None
        if traffic.get("compressor", "none") != "none":
            from repro.compress import get_compressor

            self.compressor = get_compressor(traffic["compressor"])

    @property
    def n_points(self) -> int:
        return len(self.specs)

    @property
    def quorum(self) -> int:
        return int(np.ceil(self.cfg["min_fit"] * self.cfg["n_clients"]))

    def seeds(self, sweep: int) -> List[int]:
        return sweep_seeds(self.seed, sweep, self.n_points, self.traffic["point_seeds"])

    def points(self, sweep: int):
        from repro.core import EdgeClient, GridPoint, ServerConfig
        from repro.transport import TcpParams

        cfg = self.cfg
        return [
            GridPoint(
                [EdgeClient(i, dataset=d) for i, d in enumerate(self.datasets)],
                self.strategy(cfg["min_fit"]),
                TcpParams(**tcp),
                self.chaos(link),
                ServerConfig(rounds=cfg["rounds"], seed=s,
                             clients_per_round=cfg["clients_per_round"], **self.server_kw),
                compressor=self.compressor,
            )
            for (link, tcp), s in zip(self.specs, self.seeds(sweep))
        ]

    def run(self, task, points):
        from repro.core import run_fl_grid

        return run_fl_grid(task, points, eval_data=self.eval_data, **self.traffic["grid"])

    def reference_point(self, seed: int) -> Dict[str, Any]:
        cfg = self.cfg
        k = max(self.quorum, int(round(cfg["clients_per_round"] * cfg["n_clients"])))
        return self.point(seed, cfg["n_clients"], k)


class PopulationTraffic(_Traffic):
    """Cross-device rounds over a lazy million-client universe."""

    engine = "population"

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int):
        super().__init__(cfg, traffic, seed)
        self.point_seed = sweep_seeds(self.seed, 0, 1, "shared")[0]
        self.selected = int(round(cfg["goal"] * cfg["over_provision"]))
        self.link, self.tcp = spec("links", cfg["link"]), spec("tcp", cfg["tcp"])

    @property
    def quorum(self) -> int:
        return self.cfg["goal"]

    def server(self, task):
        from repro.core import FederatedServer, Population, ServerConfig
        from repro.transport import TcpParams

        cfg = self.cfg
        n, goal = cfg["population"], cfg["goal"]
        strategy = self.strategy(goal / n)
        if strategy.quorum(n) != goal:
            raise ValueError(f"quorum {strategy.quorum(n)} != goal {goal}")
        return FederatedServer(
            task,
            Population(n, lambda c: data.dataset(c, self.shard(c)),
                       max_cached_shards=self.traffic["max_cached_shards"]),
            strategy,
            tcp=TcpParams(**self.tcp),
            chaos=self.chaos(self.link),
            config=ServerConfig(
                rounds=10**9, seed=self.point_seed,
                clients_per_round=goal / n, over_provision=cfg["over_provision"],
                # the round commits on the goal count's first arrivals
                quorum_close_fraction=goal / self.selected, **self.server_kw,
            ),
            eval_data=self.eval_data,
        )

    def reference_point(self) -> Dict[str, Any]:
        return self.point(self.point_seed, self.cfg["population"], self.selected)


def build(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int):
    kinds = {"grid": GridTraffic, "population": PopulationTraffic}
    if traffic["engine"] not in kinds:
        raise ValueError(f"unknown traffic engine {traffic['engine']!r}")
    return kinds[traffic["engine"]](cfg, traffic, seed)
