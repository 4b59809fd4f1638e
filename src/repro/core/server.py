"""The federated round engine: Flower's FL loop rebuilt transport-aware.

Each simulated round:

1. liveness: chaos schedule decides which pods are up (Chaos-Mesh analog);
2. cohort selection: sample ``clients_per_round`` of the live clients
   (straggler mitigation = over-provisioning: sample more than needed and
   keep the quorum that arrives before the deadline);
3. per-client transport: handshake-if-needed -> download -> local training
   (wire idle; keepalive mechanics apply) -> upload, all through the
   analytic transport model (or DES when ``stochastic=True``) under the
   client's effective link (chaos netem overrides apply);
4. aggregation: deltas from clients that delivered before the deadline,
   weighted by example counts; quorum = min_fit_clients (Rec #3); rounds
   below quorum are *failed rounds* (Flower retries; we account the time);
5. bookkeeping: simulated wall clock, per-client connection state, history.

Local training is REAL JAX training (CNN or reduced-LM payloads); only the
network is simulated. The simulated clock therefore reflects transport +
(modeled) Pi-class compute time, while model quality evolves from the
actual optimization trajectory — this is what lets the paper's
accuracy-vs-network figures reproduce organically.

The round is a state machine with externally drivable halves:
``select_cohort`` (liveness/selection) -> transport (``run_transport``
locally, or a grid-level plane) -> ``finish_transport`` (deliveries,
quorum, FitJob) -> ``execute_fit`` -> ``finish_round``. The grid engine
drives many servers through these halves in lockstep and hoists the
middle (stochastic transport) and the fit into shared planes; see
``ServerConfig.rng_streams`` for the stream discipline that keeps this
hoisting bitwise-safe, and docs/architecture.md for the full contract.
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.chaos import ChaosSchedule
from repro.checkpoint.store import CheckpointManager
from repro.compress import Compressor, none_compressor
from repro.core.client import EdgeClient, LocalTask
from repro.core.population import Population
from repro.core.stateplane import StatePlane
from repro.core.strategy import Strategy
from repro.transport import LinkProfile, TcpParams, client_round as analytic_round
from repro.transport.des import (
    delivery_events,
    sim_client_round,
    sim_cohort_round,
    sim_grid_round,
)
from repro.transport.params import RetryPolicy
from repro.utils import tree_stack, tree_unstack
from repro.utils.spans import span, to_host


@dataclass
class RoundRecord:
    round_idx: int
    t_start: float
    t_end: float
    selected: int
    delivered: int
    failed_round: bool
    reconnects: float
    metrics: Dict[str, float] = field(default_factory=dict)
    events: List[Any] = field(default_factory=list)
    # selected client ids in cohort (selection-draw) order — the observable
    # the split-stream contract is asserted on: at a fixed seed this
    # sequence must not depend on which transport engine sampled the round
    selected_ids: List[int] = field(default_factory=list)
    # failed rounds carry why: "no_live_quorum" | "quorum" |
    # "server_restart" | a quarantine cause ("non_finite_loss" /
    # "non_finite_delta"); empty for successful rounds
    cause: str = ""
    # partial-progress telemetry (reliability layer): total acked wire
    # bytes across the cohort's exchanges this round, and the subset
    # acked by exchanges that ultimately FAILED — wasted work unless a
    # resume= re-attempt picked the frontier back up. Defaults keep
    # RoundRecord(**r) checkpoint restores from older runs working.
    bytes_acked: float = 0.0
    wasted_bytes: float = 0.0


@dataclass
class History:
    rounds: List[RoundRecord] = field(default_factory=list)
    eval_metrics: List[Dict[str, float]] = field(default_factory=list)
    # fault-domain outcome for the whole run: "healthy" until the point is
    # quarantined ("diverged", non-finite loss/delta) or declared dead
    # ("failed", max_consecutive_failures); ``cause`` carries the trigger
    status: str = "healthy"
    cause: str = ""

    @property
    def total_time(self) -> float:
        return self.rounds[-1].t_end if self.rounds else 0.0

    @property
    def completed_rounds(self) -> int:
        return sum(0 if r.failed_round else 1 for r in self.rounds)

    def final_accuracy(self) -> Optional[float]:
        for m in reversed(self.eval_metrics):
            if "accuracy" in m:
                return m["accuracy"]
        return None

    def summary(self) -> Dict[str, float]:
        return {
            "rounds": len(self.rounds),
            "completed_rounds": self.completed_rounds,
            "total_time_s": self.total_time,
            "final_accuracy": self.final_accuracy() or float("nan"),
            "mean_reconnects": float(
                np.mean([r.reconnects for r in self.rounds]) if self.rounds else 0.0
            ),
            "status": self.status,
            "cause": self.cause,
        }


@dataclass
class FitJob:
    """Work order for one scenario-round's local training, produced by
    ``FederatedServer.begin_round`` and consumed by ``finish_round``. The
    grid engine collects FitJobs across sweep points and executes their
    union as one plane dispatch; the per-point ``run`` loop executes them
    one at a time."""

    rnd: int
    record: RoundRecord
    clients: List[EdgeClient]  # delivering clients, delivery order
    arrivals: List[float]
    payload_bytes: int  # UPLOAD wire size (compressed; byte accounting)
    steps: int
    prox_mu: float


@dataclass
class PendingRound:
    """Selected cohort awaiting transport: the output of
    ``FederatedServer.select_cohort`` and the input its transport phase
    (``finish_transport``) consumes alongside sampled outcomes.

    This is the seam the grid engine's fused transport plane cuts at: the
    driver collects PendingRounds across sweep points, samples every
    point's transport as one ``sim_grid_round`` call, and hands each
    point's row slice back to ``finish_transport``. Payload bytes are
    asymmetric — ``upload_bytes`` is the compressor's exact wire size for
    the current global params, ``download_bytes`` the full model
    (``LocalTask.update_bytes``)."""

    rnd: int
    record: RoundRecord
    cohort: List[EdgeClient]  # selection order
    links: List[LinkProfile]  # effective link per cohort member
    local_times: np.ndarray  # [k] wire-idle local-training seconds
    connected: np.ndarray  # [k] pre-round connection state
    upload_bytes: int
    download_bytes: int


@dataclass
class ServerConfig:
    rounds: int = 20
    clients_per_round: float = 1.0  # fraction of live clients selected
    local_steps: int = 10
    round_deadline: float = 600.0  # s; stragglers beyond this are dropped
    base_step_cost: float = 0.5  # s per local step on the 0.5 vCPU Pi class
    eval_every: int = 1
    stochastic: bool = False  # True => event-granular DES per client
    seed: int = 0
    # training failure semantics: how many consecutive failed rounds before
    # the run is declared dead ("no training", paper Fig 3 beyond 5 s)
    max_consecutive_failures: int = 5
    # straggler mitigation: select over_provision x quorum extra clients and
    # close the round at the first `quorum_close_fraction` of arrivals
    # (Bonawitz et al. over-selection; the paper's deadline generalized)
    over_provision: float = 1.0
    quorum_close_fraction: float = 1.0
    # Event-driven asynchronous engine (paper SecII: "the asynchronous
    # nature of FL allows clients to send updates independently"; FTTE,
    # arxiv 2510.03165, for the buffered staleness-aware formulation).
    # Rounds become dispatch TICKS: each tick dispatches fresh clients
    # against the current model, pushes their (delivery_time, update)
    # events onto a priority queue, then lands queued events in delivery
    # order into a FedBuff-style buffer. When the buffer reaches
    # ``async_buffer_k`` the whole buffer aggregates in one stacked pass,
    # each update down-weighted by (1 + staleness)^-alpha where staleness
    # is the number of model versions (buffer flushes) since the update's
    # anchor was dispatched. Failed flows and stragglers past
    # ``round_deadline`` are dropped at the transport seam — nothing ever
    # blocks on the slowest flow — and a client that dies mid-flight
    # (chaos ``alive()`` checked at LAND time) drops its update. A tick
    # landing zero updates is the async analog of a failed round and
    # counts toward ``max_consecutive_failures``.
    async_mode: bool = False
    staleness_alpha: float = 0.5
    # buffer-flush threshold (FedBuff's K). 1 = apply every update on
    # arrival; robust strategies (trimmed_mean/median/krum) require >= 2
    # because their order statistics degenerate on a single update.
    async_buffer_k: int = 1
    # cap on concurrently in-flight clients (None = no cap beyond the
    # cohort fraction): a tick dispatches at most
    # async_concurrency - len(in_flight) new clients.
    async_concurrency: Optional[int] = None
    # batched cohort engine: vectorized transport sampling, one fused
    # local-training dispatch for the whole cohort, and kernel-backed
    # stacked-delta aggregation. In the default analytic transport mode it
    # is RNG-stream-compatible with the sequential engine: same seed =>
    # same cohort/transport outcomes and (numerically equivalent) training
    # trajectory. With stochastic=True the cohort MC samples the same
    # distributions but with a different draw order, so the two engines
    # are distribution-equivalent, not draw-for-draw identical.
    batched: bool = False
    # transport engine selector (stochastic mode only). "default" keeps
    # sim_cohort_round's draw discipline; "fused_transport" routes the
    # cohort through sim_grid_round's shared-rng plane (and implies
    # rng_streams="split"). Both engines now bill ASYMMETRIC payloads —
    # uploads carry the compressor's exact wire size, downloads the full
    # model (LocalTask.update_bytes) — so the flag's remaining delta is
    # the draw order, and it is the entry point the grid driver extends
    # to an [S*C]-row plane across sweep points (run_fl_grid transport=).
    engine: str = "default"
    # RNG stream discipline. "single" (the seed-compatible historical
    # stream): ONE generator drives cohort selection, transport sampling,
    # and batch plans in interleaved consumption order — bitwise identical
    # to every release before the begin_round split. "split": two derived,
    # independently-forkable streams, fold_in-keyed per (seed, stream,
    # round) — the COHORT stream (selection draws first, then batch plans)
    # and the TRANSPORT stream. Because both are re-derived each round,
    # a point's selection sequence is bitwise invariant to which engine
    # sampled transport (per-point loop, per-scenario parity plane, or the
    # grid's shared fused plane) and to how many draws transport consumed.
    # engine="fused_transport" implies "split".
    rng_streams: str = "single"
    # Where stochastic transport is SAMPLED. "host" keeps the numpy
    # Monte-Carlo plane (the parity oracle). "device" routes the cohort
    # through the jax transport plane (repro.transport.plane): the whole
    # round's flow simulation — SYN ladder, AIMD windows, RTO backoff,
    # keepalive scan — runs as one jit dispatch on counter-based
    # jax.random streams keyed per (seed, stream, round). Device draws are
    # decorrelated from every numpy stream by construction, so the
    # discipline is ALWAYS effectively "split" (transport consumes zero
    # host draws; selection sequences are engine-invariant). Requires
    # stochastic=True and batched=True — there is no analytic or
    # sequential device path. Host/device outcome parity is the
    # stream-mapping contract in repro.transport.plane's module docs:
    # exact on degenerate (loss=0, jitter=0) rows, distributional
    # elsewhere.
    transport_backend: str = "host"
    # Application-level within-round retry (FedComm-style): failed clients
    # re-attempt the whole round exchange under the policy's exponential
    # backoff/jitter/budget, in both the host DES and the device plane
    # (see repro.transport.params.RetryPolicy). The policy's deadline_cap
    # is additionally capped at round_deadline. Stochastic engines only —
    # the analytic model exposes the closed form via
    # repro.transport.model.retry_round instead.
    retry: Optional[RetryPolicy] = None
    # Reliability profile override (see repro.transport.params
    # TRANSPORT_PROFILES): None keeps the TcpParams handed to the server
    # untouched; a profile name re-tags it at construction via
    # transport_profile(name, base=tcp). "zero_rtt" models QUIC-style
    # session resumption in every transport engine — the round's first
    # handshake cannot die on the SYN budget, later reconnects within
    # the round are free 0-RTT resumptions off the session ticket.
    transport_profile: Optional[str] = None
    # Per-point quarantine: a round producing a non-finite client loss or
    # a non-finite delta sum is REJECTED before compression/aggregation
    # (global params and residual plane stay at the round boundary), the
    # point terminates with History.status="diverged" + cause instead of
    # poisoning downstream state or raising. Detection is read-only, so
    # healthy runs are bitwise unaffected.
    quarantine: bool = True
    # Per-client state storage (error-feedback residual plane today;
    # FedDyn/SCAFFOLD per-client state tomorrow — see
    # repro.core.stateplane). "dense" materializes one row per
    # population slot ([N_clients, ...], the PR-4 layout, bitwise
    # identical to every release before the StatePlane refactor).
    # "sparse" keeps a compacted O(touched-clients) buffer keyed by a
    # host slot map — required reading for million-client populations,
    # bitwise equal to dense on every History observable (compressor
    # planes consume row values, never row positions).
    state_plane: str = "dense"

    def __post_init__(self):
        if self.state_plane not in ("dense", "sparse"):
            raise ValueError(f"unknown state_plane {self.state_plane!r}")
        # typos here would silently select the legacy stream discipline
        # and silently exclude points from the grid's transport hoist
        if self.engine not in ("default", "fused_transport"):
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.rng_streams not in ("single", "split"):
            raise ValueError(f"unknown rng_streams {self.rng_streams!r}")
        if self.transport_backend not in ("host", "device"):
            raise ValueError(f"unknown transport_backend {self.transport_backend!r}")
        if self.transport_backend == "device" and not (self.stochastic and self.batched):
            raise ValueError(
                "transport_backend='device' requires stochastic=True and "
                "batched=True (the device plane is a Monte-Carlo cohort "
                "sampler; there is no analytic or sequential device path)"
            )
        if self.retry is not None and not self.stochastic:
            raise ValueError(
                "retry= requires stochastic=True: the retry ladder is a "
                "property of the event-granular engines (host DES / device "
                "plane); for the analytic model use "
                "repro.transport.model.retry_round"
            )
        if self.transport_profile is not None:
            from repro.transport.params import TRANSPORT_PROFILES

            if self.transport_profile not in TRANSPORT_PROFILES:
                raise ValueError(
                    f"unknown transport_profile {self.transport_profile!r}; "
                    f"expected one of {TRANSPORT_PROFILES} (or None)"
                )
        if self.async_buffer_k < 1:
            raise ValueError("async_buffer_k must be >= 1")
        if self.async_concurrency is not None and self.async_concurrency < 1:
            raise ValueError("async_concurrency must be >= 1 (or None)")


# stream tags for the split-rng discipline (spawn_key components).
# _GRID_STREAM seeds the grid driver's SHARED fused-transport stream — a
# distinct tag so it never collides bitwise with any point's private
# transport stream (points and grids commonly share seed 0).
_COHORT_STREAM = 1
_TRANSPORT_STREAM = 2
_GRID_STREAM = 3
# The grid's fused host pass for RELIABILITY points (zero_rtt profile or
# resume= retry): their stage masks consume the shared numpy stream in a
# different order, so they get their own tag — pure-TCP restart-from-zero
# points keep consuming _GRID_STREAM exactly as before the reliability
# layer existed. (The device plane needs no such split: its draws are
# unconditional and where-gated, so co-scheduled reliability rows cannot
# shift a plain row's stream.)
_GRID_ZR_STREAM = 4


def derive_rng(seed: int, stream: int, rnd: int) -> np.random.Generator:
    """Fold-in-keyed generator: an independent, reproducible stream per
    (seed, stream tag, round). numpy's SeedSequence spawn keys give the
    same independence guarantee jax.random.fold_in gives PRNGKeys — equal
    keys yield bitwise-equal streams, distinct keys decorrelated ones."""
    return np.random.default_rng(
        np.random.SeedSequence(entropy=seed, spawn_key=(stream, rnd))
    )


class FederatedServer:
    def __init__(
        self,
        task: LocalTask,
        clients: List[EdgeClient],
        strategy: Strategy,
        *,
        tcp: TcpParams,
        chaos: ChaosSchedule,
        config: ServerConfig,
        compressor: Optional[Compressor] = None,
        eval_data: Optional[Dict[str, np.ndarray]] = None,
        eval_fn: Optional[Any] = None,
    ):
        self.task = task
        self.clients = clients
        self.strategy = strategy
        if config.transport_profile is not None:
            from repro.transport.params import transport_profile

            tcp = transport_profile(config.transport_profile, base=tcp)
        self.tcp = tcp
        self.chaos = chaos
        self.config = config
        self.compressor = compressor or none_compressor()
        self.eval_data = eval_data
        # eval hook: the grid engine injects a provenance-memoized wrapper
        # so sweep points sharing a trajectory evaluate once
        self._evaluate = eval_fn or task.evaluate
        self.rng = np.random.default_rng(config.seed)
        # split-stream discipline: select_cohort re-derives self.rng (the
        # cohort stream) and this transport stream at each round boundary
        self._transport_rng = None
        import jax

        if config.async_mode and strategy.robust and config.async_buffer_k < 2:
            raise ValueError(
                f"async_buffer_k={config.async_buffer_k} with robust "
                f"strategy {strategy.name!r}: order-statistic aggregation "
                "over a buffer of one silently degenerates to identity "
                "(the single update IS its own trimmed mean/median/krum "
                "pick); use async_buffer_k >= 2 or a weighted-mean strategy"
            )
        self.global_params = task.init_fn(jax.random.PRNGKey(config.seed))
        self.history = History()
        # round state-machine position (begin_round/finish_round advance it)
        self.sim_time = 0.0
        self.consecutive_failures = 0
        self.terminated = False
        # --- event-driven async engine state (config.async_mode) ---
        # heap of (t_land_abs, seq, event) over in-flight updates; seq is
        # the dispatch sequence number — the deterministic tie-break AND
        # the heap's total order (events never compare dicts)
        self._event_queue: List[Any] = []
        self._event_seq = 0
        # landed-but-unflushed updates (FedBuff buffer), land order
        self._async_buffer: List[Dict[str, Any]] = []
        # client_ids with an update still in the queue (never re-dispatched)
        self._in_flight: set = set()
        # staleness clock: number of buffer flushes applied so far
        self.model_version = 0
        # transient per-tick outputs for the grid driver: provenance tokens
        # for the tick's dispatched rows (set by the driver before
        # finish_round) and the flush descriptor of the last tick (None
        # when the tick did not flush)
        self._plane_row_keys: Optional[tuple] = None
        self._last_flush: Optional[Dict[str, Any]] = None
        # grid hook, called (self, rnd) right after a tick's flush and
        # BEFORE eval: the driver advances this point's provenance key so
        # the memoized eval caches on the post-flush trajectory
        self._async_prov_hook = None
        # plane-resident error feedback: a StatePlane of per-client f32
        # residual rows (dense or sparse per config.state_plane),
        # gathered/scattered inside the compressor's donated jit (lazily
        # allocated on the first compressed stacked round). The
        # sequential engine keeps using per-client EdgeClient.residual.
        self._residual_plane: Optional[StatePlane] = None
        # lazy population universe: client ids ARE state slots, and the
        # O(population) id-keyed slot map is skipped entirely
        self._population: Optional[Population] = (
            clients if isinstance(clients, Population) else None
        )
        if self._population is not None and config.async_mode:
            raise ValueError(
                "Population requires the synchronous engines: the async "
                "tick loop tracks per-client in-flight state by slot map; "
                "pass a materialized client list for async_mode"
            )
        self._client_slot = (
            None
            if self._population is not None
            else {id(c): i for i, c in enumerate(self.clients)}
        )

    # ------------------------------------------------------------------
    @property
    def split_streams(self) -> bool:
        """True when selection/plan draws and transport draws come from the
        two derived per-round streams (see ServerConfig.rng_streams)."""
        return (
            self.config.rng_streams == "split"
            or self.config.engine == "fused_transport"
            or self.config.transport_backend == "device"
        )

    def _round_transport_rng(self) -> np.random.Generator:
        """The generator transport sampling must consume this round: the
        derived per-round transport stream under the split discipline, the
        shared interleaved stream otherwise."""
        return self._transport_rng if self.split_streams else self.rng

    def _effective_retry(self) -> Optional[RetryPolicy]:
        """The configured RetryPolicy with its deadline cap resolved
        against the server's round_deadline (re-attempts finishing past
        the deadline could never deliver, so waiting them out is pure
        clock waste); None when retry is off."""
        r = self.config.retry
        if r is None or r.max_retries <= 0:
            return None
        cap = min(r.deadline_cap, self.config.round_deadline)
        return r if cap == r.deadline_cap else r.replace(deadline_cap=cap)

    # ------------------------------------------------------------------
    def _client_transport(
        self,
        client: EdgeClient,
        link: LinkProfile,
        local_time: float,
        upload_bytes: int,
        download_bytes: int,
    ):
        """Sequential per-client transport. Returns (completed, time,
        reconnects, bytes_acked). Payloads are asymmetric:
        ``upload_bytes`` is the compressed wire size, ``download_bytes``
        the full model; ``bytes_acked`` is the exchange's acked frontier
        (full payload on success, partial progress on failure)."""
        rng = self._round_transport_rng()
        if self.config.stochastic:
            out = sim_client_round(
                self.tcp,
                link,
                update_bytes=upload_bytes,
                local_train_time=local_time,
                rng=rng,
                connected=client.connected,
                download_bytes=download_bytes,
                retry=self._effective_retry(),
            )
            return out.success, out.time, out.reconnects, float(out.bytes_acked)
        out = analytic_round(
            self.tcp,
            link,
            update_bytes=upload_bytes,
            local_train_time=local_time,
            connected=client.connected,
            download_bytes=download_bytes,
        )
        completed = rng.random() < out.p_complete
        t = out.expected_time if math.isfinite(out.expected_time) else self.config.round_deadline
        ba = float(upload_bytes + download_bytes) if completed else 0.0
        return completed, t, out.reconnects, ba

    # ------------------------------------------------------------------
    def _cohort_transport(self, pending: PendingRound):
        """Vectorized transport for the whole cohort.

        Returns (completed [k] bool, time [k], reconnects [k],
        bytes_acked [k]). In analytic mode the completion Bernoullis are
        drawn as one batch — numpy Generators produce the identical
        stream for ``rng.random(k)`` and k scalar draws, so outcomes
        match the sequential per-client loop draw-for-draw at equal seed.
        """
        cfg = self.config
        cohort, links = pending.cohort, pending.links
        local_times = pending.local_times
        rng = self._round_transport_rng()
        if cfg.stochastic:
            connected = pending.connected
            if cfg.transport_backend == "device":
                # device-resident plane: the S=1 case of the grid's fused
                # [S*C] program — one jit dispatch for the whole cohort's
                # flow simulation, keyed on this round's transport stream.
                from repro.transport.plane import (
                    sim_grid_round_device,
                    transport_plane_key,
                )

                out = sim_grid_round_device(
                    self.tcp,
                    [links],
                    update_bytes=np.full(
                        (1, len(cohort)), pending.upload_bytes, np.int64
                    ),
                    download_bytes=np.full(
                        (1, len(cohort)), pending.download_bytes, np.int64
                    ),
                    local_train_times=local_times[None],
                    connected=connected[None],
                    key=transport_plane_key(cfg.seed, _TRANSPORT_STREAM, pending.rnd),
                    retry=self._effective_retry(),
                )
                succ, t, rc, ba = to_host(
                    (out.success, out.time, out.reconnects, out.bytes_acked), "transport"
                )
                return (
                    succ[0],
                    np.asarray(t, float)[0],
                    np.asarray(rc, float)[0],
                    np.asarray(ba, float)[0],
                )
            if cfg.engine == "fused_transport":
                # opt-in shared-rng plane (sim_grid_round fused mode): the
                # S=1 special case of the grid driver's (S, C) transport
                # plane, draw-for-draw identical to the default path.
                out = sim_grid_round(
                    self.tcp,
                    [links],
                    update_bytes=np.full(
                        (1, len(cohort)), pending.upload_bytes, np.int64
                    ),
                    download_bytes=np.full(
                        (1, len(cohort)), pending.download_bytes, np.int64
                    ),
                    local_train_times=local_times[None],
                    rng=rng,
                    connected=connected[None],
                    retry=self._effective_retry(),
                )
                return (
                    out.success[0],
                    out.time[0],
                    out.reconnects[0].astype(float),
                    out.bytes_acked[0].astype(float),
                )
            out = sim_cohort_round(
                self.tcp,
                links,
                update_bytes=pending.upload_bytes,
                local_train_times=local_times,
                rng=rng,
                connected=connected,
                download_bytes=pending.download_bytes,
                retry=self._effective_retry(),
            )
            return (
                out.success,
                out.time,
                out.reconnects.astype(float),
                out.bytes_acked.astype(float),
            )
        outs = [
            analytic_round(
                self.tcp,
                link,
                update_bytes=pending.upload_bytes,
                local_train_time=lt,
                connected=c.connected,
                download_bytes=pending.download_bytes,
            )
            for c, link, lt in zip(cohort, links, local_times)
        ]
        p = np.array([o.p_complete for o in outs])
        completed = rng.random(len(cohort)) < p
        times = np.array(
            [
                o.expected_time if math.isfinite(o.expected_time) else cfg.round_deadline
                for o in outs
            ]
        )
        wire = float(pending.upload_bytes + pending.download_bytes)
        return (
            completed,
            times,
            np.array([o.reconnects for o in outs]),
            np.where(completed, wire, 0.0),
        )

    # ------------------------------------------------------------------
    def _fail_round(self, record: RoundRecord, cause: str = "quorum") -> None:
        self.sim_time += self.config.round_deadline
        record.cause = cause
        crash = self.chaos.server_restart_in(record.t_start, self.sim_time)
        if crash is not None:
            # the server also died while waiting out this failed round:
            # every client connection drops and the downtime extends the
            # wait when it outlasts the deadline window
            for c in self._state_clients():
                c.connected = False
            self.sim_time = max(self.sim_time, crash[0] + crash[1])
        record.t_end = self.sim_time
        record.failed_round = True
        self.history.rounds.append(record)
        self.consecutive_failures += 1
        if self.consecutive_failures >= self.config.max_consecutive_failures:
            self.terminated = True
            self.history.status = "failed"
            self.history.cause = "max_consecutive_failures"

    def _abort_round_server_restart(self, record: RoundRecord, crash) -> None:
        """A ``server_restart`` chaos event landed inside this round's
        span: every in-flight contribution is lost, global params and the
        residual plane stay at the round boundary (the in-memory
        equivalent of resuming from the last ``checkpoint_dir``
        checkpoint), all client connections drop (the crash kills them;
        survivors re-handshake next round), and the clock jumps to
        crash + downtime. Deterministic — no RNG is consumed — so engine
        parity is preserved."""
        t_crash, downtime = crash
        record.failed_round = True
        record.cause = "server_restart"
        for c in self._state_clients():
            c.connected = False
        self.sim_time = t_crash + downtime
        record.t_end = self.sim_time
        self.history.rounds.append(record)
        self.consecutive_failures += 1
        if self.consecutive_failures >= self.config.max_consecutive_failures:
            self.terminated = True
            self.history.status = "failed"
            self.history.cause = "max_consecutive_failures"

    def _divergence_cause(self, stacked, deltas, per_metrics) -> Optional[str]:
        """Quarantine trigger scan, read-only: a non-finite client loss
        (free — metrics are already on the host) or a non-finite stacked/
        listed delta sum (one fused device reduction; NaN/Inf propagate
        through a plain sum). Returns the cause string or None."""
        with span("divergence"):
            for m in per_metrics:
                v = m.get("loss")
                if v is not None and not math.isfinite(float(v)):
                    return "non_finite_loss"
            tree = stacked if stacked is not None else deltas
            leaves = jax.tree.leaves(tree) if tree is not None else []
            if leaves:
                import jax.numpy as jnp

                total = float(to_host(sum(jnp.sum(leaf) for leaf in leaves), "divergence"))
                if not math.isfinite(total):
                    return "non_finite_delta"
            return None

    def _quarantine_round(self, job: FitJob, cause: str) -> None:
        """Reject the round's update and retire the point: params and the
        residual plane stay at the round boundary (detection runs BEFORE
        compression, so error feedback never ingests non-finite rows), the
        round is recorded failed with its cause, and the server terminates
        with status "diverged" instead of raising — in a grid, only this
        row is lost."""
        record = job.record
        record.failed_round = True
        record.cause = cause
        self.sim_time += min(max(job.arrivals), self.config.round_deadline)
        record.t_end = self.sim_time
        self.history.rounds.append(record)
        self.terminated = True
        self.history.status = "diverged"
        self.history.cause = cause

    def select_cohort(self, rnd: int) -> Optional[PendingRound]:
        """Pre-transport half of ``begin_round``: liveness, cohort
        selection, and the round's effective links/payloads. Returns a
        PendingRound for the transport phase, or None when the round
        already failed for lack of live clients (recorded; ``terminated``
        is set when the failure budget is spent).

        Under the split-stream discipline this is also the round boundary
        for RNG state: the cohort stream (selection draws first, batch-plan
        draws after) and the transport stream are both re-derived here,
        fold_in-keyed on (seed, stream, round) — which is what makes the
        selection sequence bitwise invariant to the transport engine."""
        with span("select") as s:
            pending = self._select_cohort(rnd)
            if pending is not None:
                s.set_metadata(cohort=len(pending.cohort))
            return pending

    def _select_cohort(self, rnd: int) -> Optional[PendingRound]:
        cfg = self.config
        if self.split_streams:
            self.rng = derive_rng(cfg.seed, _COHORT_STREAM, rnd)
            self._transport_rng = derive_rng(cfg.seed, _TRANSPORT_STREAM, rnd)
        t = self.sim_time
        if cfg.async_mode:
            return self._select_cohort_async(rnd, t)
        n_total = len(self.clients)
        if self._population is not None:
            # lazy universe: live ids without materializing clients.
            # live_ids=None is the O(1) fast path (no client-killing
            # chaos => all n ids live, id order) — the draw below is
            # then identical to the dense filter-then-choice.
            live = None
            live_ids = self._population.live_ids(self.chaos, t)
            n_live = n_total if live_ids is None else len(live_ids)
        else:
            live = [c for c in self.clients if self.chaos.alive(t, c.client_id)]
            n_live = len(live)
        quorum = self.strategy.quorum(n_total)
        record = RoundRecord(rnd, t, t, 0, 0, False, 0.0)

        if n_live < quorum:
            # Flower blocks until min_fit clients are available; account
            # the wait as a failed round of deadline length.
            self._fail_round(record, cause="no_live_quorum")
            return None

        k = max(quorum, int(round(cfg.clients_per_round * n_live)))
        k = min(int(round(k * max(cfg.over_provision, 1.0))), n_live)
        idx = self.rng.choice(n_live, size=k, replace=False)
        if live is None:
            ids = idx if live_ids is None else live_ids[idx]
            cohort = [self._population.client(int(cid)) for cid in ids]
        else:
            cohort = [live[i] for i in idx]
        record.selected = k
        record.selected_ids = [c.client_id for c in cohort]

        links = [
            c.link_override if c.link_override is not None
            else self.chaos.link_at(t, c.client_id)
            for c in cohort
        ]
        local_times = np.array(
            [cfg.local_steps * c.step_time(cfg.base_step_cost) for c in cohort]
        )
        return PendingRound(
            rnd=rnd,
            record=record,
            cohort=cohort,
            links=links,
            local_times=local_times,
            connected=np.array([c.connected for c in cohort], bool),
            upload_bytes=self.compressor.wire_bytes(self.global_params),
            download_bytes=self.task.update_bytes,
        )

    def _select_cohort_async(self, rnd: int, t: float) -> PendingRound:
        """Async dispatch half of a tick: select fresh clients to dispatch
        against the CURRENT model. Candidates are live clients without an
        update already in flight; ``async_concurrency`` caps the total in
        flight. Unlike the sync path there is no quorum gate and no failed
        round here — a tick with nothing to dispatch still drains the
        event queue (the PendingRound just carries an empty cohort)."""
        cfg = self.config
        record = RoundRecord(rnd, t, t, 0, 0, False, 0.0)
        live = [
            c
            for c in self.clients
            if self.chaos.alive(t, c.client_id)
            and c.client_id not in self._in_flight
        ]
        budget = len(live)
        if cfg.async_concurrency is not None:
            budget = max(cfg.async_concurrency - len(self._in_flight), 0)
        k = 0
        if live and budget > 0:
            k = max(1, int(round(cfg.clients_per_round * len(live))))
            k = min(k, budget, len(live))
        if k > 0:
            idx = self.rng.choice(len(live), size=k, replace=False)
            cohort = [live[i] for i in idx]
        else:
            cohort = []
        record.selected = k
        record.selected_ids = [c.client_id for c in cohort]
        links = [
            c.link_override if c.link_override is not None
            else self.chaos.link_at(t, c.client_id)
            for c in cohort
        ]
        local_times = np.array(
            [cfg.local_steps * c.step_time(cfg.base_step_cost) for c in cohort]
        )
        return PendingRound(
            rnd=rnd,
            record=record,
            cohort=cohort,
            links=links,
            local_times=local_times,
            connected=np.array([c.connected for c in cohort], bool),
            upload_bytes=self.compressor.wire_bytes(self.global_params),
            download_bytes=self.task.update_bytes,
        )

    def run_transport(self, pending: PendingRound):
        """Sample the pending round's transport on this server's own
        streams: the batched cohort draw discipline or the sequential
        per-client loop. Returns (completed [k], times [k], reconnects
        [k], bytes_acked [k]) — the tuple ``finish_transport`` consumes,
        and the same shape the grid driver's shared plane produces per
        point."""
        if len(pending.cohort) == 0:  # async drain-only tick
            z = np.zeros(0, float)
            return np.zeros(0, bool), z, z, z
        with span("transport", rows=len(pending.cohort)):
            if self.config.batched:
                return self._cohort_transport(pending)
            comp, times, recon, acked = [], [], [], []
            for client, link, lt in zip(pending.cohort, pending.links, pending.local_times):
                done, ct, rc, ba = self._client_transport(
                    client, link, float(lt), pending.upload_bytes, pending.download_bytes
                )
                comp.append(done)
                times.append(ct)
                recon.append(rc)
                acked.append(ba)
            return (
                np.array(comp, bool),
                np.array(times, float),
                np.array(recon, float),
                np.array(acked, float),
            )

    def _record_bytes(self, record: RoundRecord, completed, bytes_acked) -> None:
        """Fold partial-progress telemetry into the round record: total
        acked wire bytes, and the failed-exchange subset (wasted work)."""
        if bytes_acked is None:
            return
        ba = np.asarray(bytes_acked, float)
        if ba.size == 0:
            return
        record.bytes_acked += float(ba.sum())
        record.wasted_bytes += float(ba[~np.asarray(completed, bool)].sum())

    def finish_transport(
        self, pending: PendingRound, completed, times, reconnects,
        bytes_acked=None,
    ) -> Optional[FitJob]:
        """Post-transport half of ``begin_round``: apply sampled outcomes
        — connection state, deliveries under the deadline, straggler
        close, quorum — and emit the round's FitJob (or record a failed
        round and return None). ``completed``/``times``/``reconnects`` are
        [k] arrays in cohort order, from ``run_transport`` or from one
        point's row slice of the grid driver's fused transport plane;
        ``bytes_acked`` (optional, [k]) carries the exchanges' acked
        frontiers into the round's wasted-work telemetry."""
        with span("finish_transport", rows=len(pending.cohort)):
            if self.config.async_mode:
                return self._finish_transport_async(
                    pending, completed, times, reconnects, bytes_acked
                )
            return self._finish_transport_sync(
                pending, completed, times, reconnects, bytes_acked
            )

    def _finish_transport_sync(
        self, pending: PendingRound, completed, times, reconnects, bytes_acked
    ) -> Optional[FitJob]:
        cfg = self.config
        record = pending.record
        quorum = self.strategy.quorum(len(self.clients))
        record.reconnects += float(np.sum(np.asarray(reconnects, float)))
        self._record_bytes(record, completed, bytes_acked)
        deliveries = []
        for client, done, ct in zip(pending.cohort, completed, times):
            client.connected = bool(done)  # failed exchange leaves conn dead
            if done and ct <= cfg.round_deadline:
                deliveries.append((client, float(ct)))

        # straggler mitigation: close the round once the fastest
        # quorum_close_fraction of the over-provisioned cohort arrived
        if cfg.quorum_close_fraction < 1.0 and len(deliveries) > quorum:
            deliveries.sort(key=lambda d: d[1])
            keep = max(quorum, int(len(deliveries) * cfg.quorum_close_fraction))
            deliveries = deliveries[:keep]

        record.delivered = len(deliveries)
        if len(deliveries) < quorum:
            self._fail_round(record, cause="quorum")
            return None
        self.consecutive_failures = 0
        return FitJob(
            rnd=pending.rnd,
            record=record,
            clients=[client for client, _ in deliveries],
            arrivals=[ct for _, ct in deliveries],
            payload_bytes=pending.upload_bytes,
            steps=cfg.local_steps,
            prox_mu=self.strategy.prox_mu,
        )

    def _finish_transport_async(
        self, pending: PendingRound, completed, times, reconnects,
        bytes_acked=None,
    ) -> FitJob:
        """Async post-transport half: fold the tick's sampled flows into
        delivery EVENTS. Failed flows and stragglers past the deadline are
        dropped here — they never enter the event queue, so the server
        never blocks on them (the paper's burst-idle pathology). Always
        returns a FitJob (possibly with zero clients — the drain still
        runs); deliverable clients are listed in LAND order, and their
        deltas are computed against the CURRENT global params (the model
        snapshot the client downloaded at dispatch)."""
        cfg = self.config
        record = pending.record
        record.reconnects += float(np.sum(np.asarray(reconnects, float)))
        self._record_bytes(record, completed, bytes_acked)
        for client, done in zip(pending.cohort, completed):
            client.connected = bool(done)  # failed exchange leaves conn dead
        events = delivery_events(
            completed, times, t_start=0.0, deadline=cfg.round_deadline
        )
        return FitJob(
            rnd=pending.rnd,
            record=record,
            clients=[pending.cohort[j] for _, j in events],
            arrivals=[t for t, _ in events],
            payload_bytes=pending.upload_bytes,
            steps=cfg.local_steps,
            prox_mu=self.strategy.prox_mu,
        )

    def begin_round(self, rnd: int) -> Optional[FitJob]:
        """Liveness, cohort selection, transport, quorum. Returns a FitJob
        when local training should run, or None for a failed round (already
        recorded; ``terminated`` is set when the failure budget is spent).

        Composed of ``select_cohort`` -> ``run_transport`` ->
        ``finish_transport``; callers that sample transport elsewhere (the
        grid engine's fused (S, C) plane) call the outer halves directly
        and skip ``run_transport``."""
        pending = self.select_cohort(rnd)
        if pending is None:
            return None
        completed, times, reconnects, bytes_acked = self.run_transport(pending)
        return self.finish_transport(
            pending, completed, times, reconnects, bytes_acked
        )

    def execute_fit(self, job: FitJob):
        """Per-point local training for one FitJob: one plane dispatch for
        the cohort (batched) or the sequential per-client loop. Returns
        (stacked [C,...] or None, deltas list, weights, per_metrics).

        Batch plans draw from ``self.rng`` — the cohort stream. Under the
        split discipline that stream was re-derived at this round's
        ``select_cohort`` (selection draws came first), so plan draws can
        never perturb a later round's selection."""
        cfg = self.config
        stacked = None  # stacked deltas [C, ...] when the batched fit ran
        deltas: List[Any] = []
        if not job.clients:  # async drain-only tick: nothing to train
            return None, [], [], []
        if cfg.batched and self.task.batched_local_fit is not None:
            stacked, weights, per_metrics = self.task.batched_local_fit(
                self.global_params,
                job.clients,
                job.steps,
                self.rng,
                job.prox_mu,
            )
            weights = list(weights)
        else:
            weights, per_metrics = [], []
            for client in job.clients:
                delta, n_ex, m = self.task.local_fit(
                    self.global_params, client, job.steps, self.rng, job.prox_mu
                )
                deltas.append(delta)
                weights.append(n_ex)
                per_metrics.append(m)
        return stacked, deltas, weights, per_metrics

    def _ensure_residual_plane(self) -> StatePlane:
        """The per-client residual StatePlane (dense or sparse per
        ``config.state_plane``), lazily allocated on the first compressed
        stacked round. Dense storage is row-for-row the legacy
        ``init_residual_plane`` layout."""
        if self._residual_plane is None:
            self._residual_plane = StatePlane(
                self.global_params,
                len(self.clients),
                storage=self.config.state_plane,
            )
        return self._residual_plane

    def client_slots(self, clients: List[EdgeClient]) -> List[int]:
        """Population-wide state slots for a list of (delivering) clients.

        Slots are stable client identities — list universes key them by
        list position, lazy populations by client id — and they are what
        grid compression provenance is keyed on. ``StatePlane.rows_for``
        maps them to physical buffer rows at dispatch time."""
        if self._client_slot is None:
            return [c.client_id for c in clients]
        return [self._client_slot[id(c)] for c in clients]

    def _state_clients(self) -> List[EdgeClient]:
        """Clients that may hold non-default mutable state: the whole
        list, or only the population's materialized clients (untouched
        lazy clients are disconnected with zero counters by
        construction, so O(population) sweeps skip them exactly)."""
        if self._population is not None:
            return self._population.active_clients()
        return self.clients

    def _client_at(self, slot: int) -> EdgeClient:
        """The client occupying a state slot (checkpoint restore path)."""
        if self._population is not None:
            return self._population.peek(slot)
        return self.clients[slot]

    def _slotted_state_clients(self):
        """(slot, client) pairs for clients that may hold per-client
        state — the checkpoint protocol's iteration surface. O(active)
        for populations, the full enumeration for lists."""
        if self._population is not None:
            return [(c.client_id, c) for c in self._population.active_clients()]
        return list(enumerate(self.clients))

    def finish_round(
        self, job: FitJob, stacked, deltas, weights, per_metrics,
        precompressed: bool = False, fault_checked: bool = False,
    ) -> None:
        """Compression, bookkeeping, aggregation, clock advance, eval.

        ``precompressed=True`` means the caller (the grid engine) already
        ran plane compression — possibly shared across sweep points with
        equal compression provenance — and ``stacked`` holds decompressed
        deltas with this server's residual plane already advanced.

        Byte accounting follows the asymmetric payload convention:
        ``job.payload_bytes`` (credited to ``client.bytes_sent``) is the
        compressed UPLOAD wire size; the full-model download was already
        billed by the transport phase via ``PendingRound.download_bytes``.
        Consumes no RNG: everything stochastic about a round happens in
        ``begin_round``/``execute_fit``."""
        with span("finish_round", rows=len(job.clients)):
            self._finish_round(job, stacked, deltas, weights, per_metrics,
                               precompressed, fault_checked)

    def _finish_round(
        self, job: FitJob, stacked, deltas, weights, per_metrics,
        precompressed: bool, fault_checked: bool,
    ) -> None:
        cfg = self.config
        rnd = job.rnd
        record = job.record
        dclients = job.clients
        arrivals = job.arrivals

        # fault domain, checked before any state mutates: a server crash
        # inside the round span loses the round outright; a quarantine
        # trigger (non-finite loss/delta) rejects it before compression so
        # the residual plane never ingests poison. ``fault_checked=True``
        # means the caller (the grid driver, which must check before its
        # SHARED compression pass) already ran both checks. The async tick
        # fault window is the full deadline horizon: every event the tick
        # can land falls in (t_start, t_start + round_deadline] — fresh
        # dispatches land within the deadline by construction, and queued
        # events were dispatched at earlier (<= t_start) ticks — so a
        # server_restart inside that window voids the tick, losing every
        # in-flight update and the buffer (crash drops server state).
        if cfg.async_mode:
            if not fault_checked:
                crash = self.chaos.server_restart_in(
                    record.t_start, record.t_start + cfg.round_deadline
                )
                if crash is not None:
                    self._abort_tick_server_restart(record, crash)
                    return
                if cfg.quarantine and dclients:
                    cause = self._divergence_cause(stacked, deltas, per_metrics)
                    if cause is not None:
                        self._quarantine_round(job, cause)
                        return
        else:
            round_time = min(max(arrivals), cfg.round_deadline)
            if not fault_checked:
                crash = self.chaos.server_restart_in(
                    record.t_start, record.t_start + round_time
                )
                if crash is not None:
                    self._abort_round_server_restart(record, crash)
                    return
                if cfg.quarantine:
                    cause = self._divergence_cause(stacked, deltas, per_metrics)
                    if cause is not None:
                        self._quarantine_round(job, cause)
                        return

        # compression: the plane path keeps the whole cohort stacked —
        # error-feedback residuals live in a [N_clients, ...] device plane
        # and the compressor's donated jit gathers the delivering rows,
        # compresses, and scatters new residuals back (bitwise identical
        # to the per-client loop). Compressors without a plane twin
        # (stateful randk) or unstacked deltas fall back to the loop.
        if self.compressor.name != "none" and not precompressed:
            plane_fn = self.compressor.compress_plane
            if stacked is not None and plane_fn is not None:
                plane = self._ensure_residual_plane()
                slots = np.asarray(self.client_slots(dclients), np.int32)
                # physical buffer rows for the cohort's slots (identity
                # under dense storage; compacted rows under sparse)
                rows = plane.rows_for(slots)
                stacked, plane.buffer = plane_fn(stacked, plane.buffer, rows)
            else:
                if stacked is not None:
                    deltas = tree_unstack(stacked)
                    stacked = None
                compressed = []
                for client, delta in zip(dclients, deltas):
                    payload, client.residual = self.compressor.compress(
                        delta, client.residual
                    )
                    compressed.append(self.compressor.decompress(payload))
                deltas = compressed

        for client, m in zip(dclients, per_metrics):
            client.rounds_participated += 1
            client.bytes_sent += job.payload_bytes
            record.metrics.update({f"client_{client.client_id}_{k}": v for k, v in m.items()})

        if cfg.async_mode:
            flushed = self._async_tick(job, stacked, deltas, weights, rnd)
            if self._async_prov_hook is not None:
                self._async_prov_hook(self, rnd)
            if (
                flushed
                and self.eval_data is not None
                and (rnd + 1) % cfg.eval_every == 0
            ):
                m = self._evaluate(self.global_params, self.eval_data)
                m["round"] = rnd
                m["t"] = self.sim_time
                self.history.eval_metrics.append(m)
            return
        if cfg.batched:
            # stacked-delta fast path: kernel-backed reduction (falls
            # back to the list path inside aggregate_stacked when the
            # strategy has no stacked twin)
            if stacked is None:
                stacked = tree_stack(deltas)
            self.global_params = self.strategy.aggregate_stacked(
                self.global_params, stacked, weights, rnd
            )
        else:
            self.global_params = self.strategy.aggregate(
                self.global_params, deltas, weights, rnd
            )

        self.sim_time += round_time
        record.t_end = self.sim_time
        self.history.rounds.append(record)

        if self.eval_data is not None and (rnd + 1) % cfg.eval_every == 0:
            m = self._evaluate(self.global_params, self.eval_data)
            m["round"] = rnd
            m["t"] = self.sim_time
            self.history.eval_metrics.append(m)

    # ------------------------------------------------------------------
    # event-driven async engine (config.async_mode)
    # ------------------------------------------------------------------
    def _abort_tick_server_restart(self, record: RoundRecord, crash) -> None:
        """Async twin of ``_abort_round_server_restart``: the crash also
        loses every in-flight update and the landed-but-unflushed buffer
        (they live in server memory), not just the tick's dispatches."""
        self._event_queue.clear()
        self._async_buffer.clear()
        self._in_flight.clear()
        self._abort_round_server_restart(record, crash)

    def _async_tick(self, job: FitJob, stacked, deltas, weights, rnd: int) -> bool:
        """Enqueue the tick's dispatched updates, then land queued events
        in delivery order until the buffer flushes (or the queue drains).
        Returns True when a flush advanced the model.

        - *Enqueue.* Each deliverable dispatch becomes a heap event at its
          absolute land time, carrying the delta (trained against the
          model version current NOW, at dispatch — that version stamp is
          the update's staleness clock) and, in grid mode, the provenance
          token the driver staged in ``_plane_row_keys``.
        - *Land.* Events pop in (t_land, seq) order. Chaos ``alive()`` is
          re-checked at LAND time: a client that died after dispatch but
          before delivery drops its update deterministically.
        - *Flush.* When the buffer reaches ``async_buffer_k``, every
          buffered update is down-weighted by (1 + staleness)^-alpha
          (staleness = model versions elapsed since its dispatch) and the
          WHOLE buffer aggregates in one stacked pass — robust strategies
          see the full buffer, never a single update. At most one flush
          per tick: the clock stops at the flush event, remaining events
          stay queued for the next tick.
        - *Clock/breaker.* The clock advances to the last landed event
          (flush or partial progress); a tick landing nothing is a failed
          tick of deadline length — the async analog of a failed round —
          and counts toward ``max_consecutive_failures``.
        """
        cfg = self.config
        record = job.record
        prov = self._plane_row_keys
        self._plane_row_keys = None
        if job.clients:
            if stacked is not None:
                deltas = tree_unstack(stacked)
            for j, (client, dt) in enumerate(zip(job.clients, job.arrivals)):
                ev = {
                    "client_id": client.client_id,
                    "slot": self._client_slot[id(client)],
                    "delta": deltas[j],
                    "weight": weights[j],
                    "version": self.model_version,
                    "prov": None if prov is None else prov[j],
                }
                heapq.heappush(
                    self._event_queue,
                    (record.t_start + float(dt), self._event_seq, ev),
                )
                self._event_seq += 1
                self._in_flight.add(client.client_id)

        landed = 0
        dropped_dead = 0
        last_land: Optional[float] = None
        flush_time: Optional[float] = None
        while self._event_queue:
            t_land, _, ev = heapq.heappop(self._event_queue)
            self._in_flight.discard(ev["client_id"])
            last_land = t_land
            if not self.chaos.alive(t_land, ev["client_id"]):
                # mid-flight death: dispatched (and billed) but gone at
                # land time — the update is dropped, deterministically
                dropped_dead += 1
                continue
            ev["t_land"] = t_land
            self._async_buffer.append(ev)
            landed += 1
            if len(self._async_buffer) >= cfg.async_buffer_k:
                flush_time = t_land
                break
        record.delivered = landed
        if dropped_dead:
            record.metrics["async_dropped_dead"] = float(dropped_dead)

        self._last_flush = None
        if flush_time is not None:
            buf = self._async_buffer
            self._async_buffer = []
            stales = [self.model_version - e["version"] for e in buf]
            ws = [(1.0 + s) ** (-cfg.staleness_alpha) for s in stales]
            if any(w != 1.0 for w in ws):
                scaled = [
                    jax.tree.map(lambda d, _w=w: d * _w, e["delta"])
                    for e, w in zip(buf, ws)
                ]
            else:
                scaled = [e["delta"] for e in buf]  # w==1.0: skip the mul
            bw = [e["weight"] for e in buf]
            if cfg.batched:
                self.global_params = self.strategy.aggregate_stacked(
                    self.global_params, tree_stack(scaled), bw, rnd
                )
            else:
                self.global_params = self.strategy.aggregate(
                    self.global_params, scaled, bw, rnd
                )
            self.model_version += 1
            record.metrics["async_flush_size"] = float(len(buf))
            self._last_flush = {
                "version": self.model_version,
                "opaque": any(e["prov"] is None for e in buf),
                # flush identity for grid provenance: which updates, how
                # stale, at what weight — enough that equal descriptors
                # applied to equal params yield bitwise-equal new params
                "events": tuple(
                    (e["prov"], int(s), float(w))
                    for e, s, w in zip(buf, stales, bw)
                ),
            }

        if landed > 0:
            # progress: updates reached the buffer (and possibly flushed)
            self.sim_time = max(
                self.sim_time,
                flush_time if flush_time is not None else last_land,
            )
            self.consecutive_failures = 0
            record.t_end = self.sim_time
            self.history.rounds.append(record)
        else:
            # nothing landed within the tick: the async failed round
            self._fail_round(record, cause="no_updates")
        return flush_time is not None

    def run(
        self,
        *,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        checkpoint_keep: int = 3,
        stop_after_round: Optional[int] = None,
    ) -> History:
        """Drive the configured number of rounds (sync) or ticks (async).

        ``checkpoint_dir`` makes the run crash-consistent with the same
        round-boundary protocol the grid driver uses: every
        ``checkpoint_every`` rounds the full boundary state persists —
        params, residual plane, server-optimizer state, RNG cursors,
        history, client state, compressor draw counters, and (async) the
        event queue, buffer, and staleness clocks — and a re-invocation
        with the same directory resumes at the first unfinished round,
        bitwise identical to the uninterrupted run. ``stop_after_round=k``
        exits cleanly once round k completes (the kill-switch the
        crash/resume tests are built on)."""
        mgr: Optional[CheckpointManager] = None
        start_round = 0
        if checkpoint_dir is not None:
            self._check_checkpointable()
            mgr = CheckpointManager(checkpoint_dir, keep=checkpoint_keep)
            start_round = self._restore_checkpoint(mgr)
        end_round = (
            self.config.rounds
            if stop_after_round is None
            else min(self.config.rounds, stop_after_round)
        )
        for rnd in range(start_round, end_round):
            if self.terminated:
                break
            with span("round", round=rnd):
                job = self.begin_round(rnd)
                if job is not None:
                    stacked, deltas, weights, per_metrics = self.execute_fit(job)
                    self.finish_round(job, stacked, deltas, weights, per_metrics)
            if mgr is not None and (rnd + 1) % checkpoint_every == 0:
                self._save_checkpoint(mgr, rnd + 1)
        return self.history

    # ------------------------------------------------------------------
    # round-boundary checkpoint protocol (per-point; the grid driver
    # composes the same building blocks across points)
    # ------------------------------------------------------------------
    def _check_checkpointable(self) -> None:
        comp = self.compressor
        if (
            comp.name != "none"
            and not comp.fingerprint
            and (comp.state_get is None or comp.state_set is None)
        ):
            raise ValueError(
                f"checkpoint_dir: compressor {comp.name!r} carries "
                "Python-side state (empty fingerprint) without state_get/"
                "state_set accessors, so the round-boundary checkpoint "
                "cannot capture it"
            )

    def _checkpoint_fingerprint(self) -> Dict[str, Any]:
        cfg = self.config
        return {
            "kind": "point",
            "seed": int(cfg.seed),
            "rounds": int(cfg.rounds),
            "n_clients": len(self.clients),
            "async_mode": bool(cfg.async_mode),
            "async_buffer_k": int(cfg.async_buffer_k),
            "strategy": self.strategy.name,
            "compressor": self.compressor.name,
        }

    def checkpoint_arrays(self) -> Dict[str, Any]:
        """The boundary state that lives in ARRAYS: params, residual
        plane, server-optimizer state, per-client sequential residuals
        (the non-plane compression fallback), and — async — the delta
        trees riding in the event queue and the flush buffer."""
        node: Dict[str, Any] = {"params": self.global_params}
        if self._residual_plane is not None:
            # dense: the full buffer, byte-identical to older releases;
            # sparse: occupied rows compacted in row order (their slots
            # ride the manifest slot_maps entry — checkpoint_slot_maps)
            node["residual"] = self._residual_plane.state_arrays()
        if self.strategy.server_state is not None:
            node["server_state"] = self.strategy.server_state
        cres = {
            f"c{j}": c.residual
            for j, c in self._slotted_state_clients()
            if c.residual is not None
        }
        if cres:
            node["cres"] = cres
        if self._event_queue:
            node["evq"] = {
                f"e{n}": ev["delta"]
                for n, (_, _, ev) in enumerate(self._event_queue)
            }
        if self._async_buffer:
            node["evb"] = {
                f"b{n}": ev["delta"]
                for n, ev in enumerate(self._async_buffer)
            }
        return node

    def checkpoint_meta(self) -> Dict[str, Any]:
        """JSON-safe boundary state: clocks, RNG cursors, history, client
        state, compressor draw counters, and the async event queue/buffer
        descriptors (their delta trees live in ``checkpoint_arrays``).
        Floats survive JSON bit-exactly, so a restore is bitwise."""
        h = self.history

        def _ev_meta(t_land, seq, ev):
            return {
                "t_land": float(t_land),
                "seq": int(seq),
                "client_id": int(ev["client_id"]),
                "slot": int(ev["slot"]),
                "weight": _jsonable(ev["weight"]),
                "version": int(ev["version"]),
                "prov": ev["prov"],
            }

        comp_state = (
            self.compressor.state_get()
            if self.compressor.state_get is not None
            else None
        )
        return {
            "sim_time": float(self.sim_time),
            "consecutive_failures": int(self.consecutive_failures),
            "terminated": bool(self.terminated),
            "status": h.status,
            "cause": h.cause,
            # generator states matter only for single-stream points
            # (split streams re-derive per round) but are cheap to carry
            "rng_state": _jsonable(self.rng.bit_generator.state),
            "transport_rng_state": (
                _jsonable(self._transport_rng.bit_generator.state)
                if self._transport_rng is not None
                else None
            ),
            # list universes save every client (legacy layout); lazy
            # populations save only touched clients, keyed by slot —
            # untouched clients are default-state by construction
            "clients": (
                None
                if self._population is not None
                else [
                    {
                        "connected": bool(c.connected),
                        "rounds_participated": int(c.rounds_participated),
                        "bytes_sent": int(c.bytes_sent),
                    }
                    for c in self.clients
                ]
            ),
            "clients_sparse": (
                {
                    str(j): {
                        "connected": bool(c.connected),
                        "rounds_participated": int(c.rounds_participated),
                        "bytes_sent": int(c.bytes_sent),
                    }
                    for j, c in self._slotted_state_clients()
                }
                if self._population is not None
                else None
            ),
            "rounds": [_jsonable(dataclasses.asdict(r)) for r in h.rounds],
            "eval_metrics": [_jsonable(m) for m in h.eval_metrics],
            "has_residual": self._residual_plane is not None,
            "residual_plane": (
                self._residual_plane.state_meta()
                if self._residual_plane is not None
                else None
            ),
            "has_server_state": self.strategy.server_state is not None,
            "residual_clients": [
                j
                for j, c in self._slotted_state_clients()
                if c.residual is not None
            ],
            "compressor_state": _jsonable(comp_state),
            # async engine state: the staleness clock, the dispatch
            # sequence cursor, and the queue/buffer in HEAP-LIST order
            # (restoring the same list preserves the heap bitwise)
            "model_version": int(self.model_version),
            "event_seq": int(self._event_seq),
            "queue": [_ev_meta(t, s, ev) for t, s, ev in self._event_queue],
            "buffer": [
                _ev_meta(ev["t_land"], -1, ev) for ev in self._async_buffer
            ],
        }

    def checkpoint_template(self, mp: Dict[str, Any]) -> Dict[str, Any]:
        """Array-tree template matching ``checkpoint_arrays`` for a fresh
        server, shaped from the saved metadata (delta trees and residuals
        are params-shaped by construction)."""
        import jax.numpy as jnp

        node: Dict[str, Any] = {"params": self.global_params}
        if mp["has_residual"]:
            # shape from the saved plane descriptor (checkpoints from
            # before the StatePlane refactor carry no descriptor: dense)
            node["residual"] = StatePlane.template_arrays(
                self.global_params, len(self.clients), mp.get("residual_plane")
            )
        if mp["has_server_state"]:
            node["server_state"] = self.strategy.server_opt.init(
                self.global_params
            )
        if mp.get("residual_clients"):
            f32 = jax.tree.map(
                lambda l: jnp.zeros(l.shape, jnp.float32), self.global_params
            )
            node["cres"] = {f"c{j}": f32 for j in mp["residual_clients"]}
        zeros = jax.tree.map(jnp.zeros_like, self.global_params)
        if mp.get("queue"):
            node["evq"] = {f"e{n}": zeros for n in range(len(mp["queue"]))}
        if mp.get("buffer"):
            node["evb"] = {f"b{n}": zeros for n in range(len(mp["buffer"]))}
        return node

    def apply_checkpoint(
        self,
        mp: Dict[str, Any],
        tree: Dict[str, Any],
        slot_maps: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Restore the boundary state captured by ``checkpoint_arrays`` +
        ``checkpoint_meta`` onto this (freshly constructed) server.

        ``slot_maps`` carries the manifest's slot-map entry (see
        ``repro.checkpoint.store``): for sparse planes, the slot each
        saved row belongs to. The restore is storage-agnostic — saved
        rows scatter into whatever storage ``config.state_plane``
        selects, so dense checkpoints resume into sparse runs and
        vice versa, bitwise on every History observable."""
        import jax.numpy as jnp

        self.global_params = jax.tree.map(jnp.asarray, tree["params"])
        if mp["has_residual"]:
            self._residual_plane = StatePlane.from_checkpoint(
                self.global_params,
                len(self.clients),
                mp.get("residual_plane"),
                tree["residual"],
                storage=self.config.state_plane,
                slots=(slot_maps or {}).get("residual"),
            )
        if mp["has_server_state"]:
            self.strategy.server_state = jax.tree.map(
                jnp.asarray, tree["server_state"]
            )
        for j in mp.get("residual_clients", []):
            self._client_at(j).residual = jax.tree.map(
                jnp.asarray, tree["cres"][f"c{j}"]
            )
        self.sim_time = float(mp["sim_time"])
        self.consecutive_failures = int(mp["consecutive_failures"])
        self.terminated = bool(mp["terminated"])
        self.history.status = mp["status"]
        self.history.cause = mp["cause"]
        self.history.rounds = [RoundRecord(**r) for r in mp["rounds"]]
        self.history.eval_metrics = [dict(m) for m in mp["eval_metrics"]]
        self.rng.bit_generator.state = mp["rng_state"]
        if mp["transport_rng_state"] is not None:
            self._transport_rng = np.random.default_rng()
            self._transport_rng.bit_generator.state = mp["transport_rng_state"]
        if mp.get("clients") is not None:
            for c, cs in zip(self.clients, mp["clients"]):
                c.connected = bool(cs["connected"])
                c.rounds_participated = int(cs["rounds_participated"])
                c.bytes_sent = int(cs["bytes_sent"])
        for j, cs in (mp.get("clients_sparse") or {}).items():
            c = self._client_at(int(j))
            c.connected = bool(cs["connected"])
            c.rounds_participated = int(cs["rounds_participated"])
            c.bytes_sent = int(cs["bytes_sent"])
        if (
            mp.get("compressor_state") is not None
            and self.compressor.state_set is not None
        ):
            self.compressor.state_set(mp["compressor_state"])
        # async engine state
        self.model_version = int(mp.get("model_version", 0))
        self._event_seq = int(mp.get("event_seq", 0))

        def _ev(em, delta):
            return {
                "client_id": int(em["client_id"]),
                "slot": int(em["slot"]),
                "delta": delta,
                "weight": em["weight"],
                "version": int(em["version"]),
                "prov": em["prov"],
            }

        self._event_queue = [
            (
                float(em["t_land"]),
                int(em["seq"]),
                _ev(em, jax.tree.map(jnp.asarray, tree["evq"][f"e{n}"])),
            )
            for n, em in enumerate(mp.get("queue", []))
        ]
        self._async_buffer = []
        for n, em in enumerate(mp.get("buffer", [])):
            ev = _ev(em, jax.tree.map(jnp.asarray, tree["evb"][f"b{n}"]))
            ev["t_land"] = float(em["t_land"])
            self._async_buffer.append(ev)
        self._in_flight = {
            ev["client_id"] for _, _, ev in self._event_queue
        }

    def checkpoint_slot_maps(self) -> Dict[str, Any]:
        """Manifest ``slot_maps`` entry: per-plane slot lists naming the
        slot each saved row belongs to, in ``state_arrays`` row order.
        Dense planes save nothing (row i IS slot i — the legacy layout),
        so pre-sparse checkpoints stay byte-compatible."""
        if (
            self._residual_plane is not None
            and self._residual_plane.storage == "sparse"
        ):
            return {"residual": self._residual_plane.slot_list()}
        return {}

    def _save_checkpoint(self, mgr: CheckpointManager, next_round: int) -> None:
        mgr.save(
            next_round,
            self.checkpoint_arrays(),
            metadata={
                "next_round": int(next_round),
                "fingerprint": self._checkpoint_fingerprint(),
                "point": self.checkpoint_meta(),
            },
            slot_maps=self.checkpoint_slot_maps(),
        )

    def _restore_checkpoint(self, mgr: CheckpointManager) -> int:
        from repro.checkpoint.store import load_tree

        step = mgr.latest_step()
        if step is None:
            return 0
        meta = mgr.metadata(step)
        if meta["fingerprint"] != self._checkpoint_fingerprint():
            raise ValueError(
                "checkpoint_dir holds a checkpoint from a DIFFERENT run "
                f"(saved {meta['fingerprint']!r} vs this server "
                f"{self._checkpoint_fingerprint()!r}); refusing to mix"
            )
        mp = meta["point"]
        tree, _ = load_tree(mgr._step_dir(step), self.checkpoint_template(mp))
        self.apply_checkpoint(mp, tree, slot_maps=mgr.slot_maps(step))
        return int(meta["next_round"])


def _jsonable(v):
    """numpy scalars -> python, tuples/namedtuples -> lists, recursively
    (round-boundary metadata must survive a JSON round-trip bit-exactly:
    floats are IEEE-exact through json, ints are arbitrary-precision)."""
    if isinstance(v, np.floating):
        return float(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return v
