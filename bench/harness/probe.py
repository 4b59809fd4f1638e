"""Wrappers the benchmark installs around the engine's entry points.

Until the program carries spans of its own, the benchmark times and names
the calls it makes into each layer from here. Every run installs the
round seam alone:

- round boundaries: the first ``FederatedServer.select_cohort`` call of
  each round (the grid calls it once per point, ``begin_round`` calls it
  too), and the end of each engine run;
- what the references need: each round's transport outcomes as
  ``finish_transport`` receives them (cohort, connection state, completed,
  arrival times) with the clients the round then committed, and, for the
  rounds the FL reference follows from a point's start, the per-leaf norms
  of the global params' change since that start (one jitted reduction
  dispatched as ``finish_round`` returns; the start is remade inside it).
  Where a window sample is followed from the program's own params
  (``TreeBuffer``), host copies of the last few trees besides.

Beyond the program's own arrays the probe holds at most one whole tree
on the device (a copy to the host in flight) and ``TreeBuffer.size`` on
the host, whatever the number of points, rounds or sweeps; ``kept`` says
how many bytes it held at most of each.

A traced run installs besides:

- spans (``jax.profiler.TraceAnnotation``) around selection, shard
  building, transport, batch plans, the fit plane, the gather of fit
  rows, the divergence check, aggregation, eval, ``finish_transport``,
  ``finish_round``, server construction and the building of each sweep's
  points;
- counters: fit rows trained, fit dispatches, eval examples (the eval
  data's leading axis), kernel bytes
  from shapes, time in selection, and XLA compilations or compile-cache
  loads (``jax.monitoring``).
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from harness import check
from harness.data import examples

SPAN_PREFIX = "bench."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize for l in jax.tree.leaves(tree))


class TreeBuffer:
    """Host copies of the trees after the last ``size`` rounds captured, up
    to round ``until`` once it is set: where a window sample starts and
    what it reaches, also where the window's end cuts it short. Each tree
    starts its copy to the host as it is captured, and its device reference
    is dropped at the next capture (or ``settle``), by when the copy is
    done: no host sync inside a round."""

    def __init__(self, size: int):
        self.size = size
        self.until: Optional[int] = None
        self.trees: Dict[int, Any] = {}  # round -> numpy tree
        self._pending: Optional[tuple] = None  # (round, device tree)
        self.device_bytes = self.host_bytes = 0  # the most held at once

    def add(self, rnd: int, tree) -> None:
        if self.until is not None and rnd > self.until:
            return
        self.settle()
        for leaf in jax.tree.leaves(tree):
            leaf.copy_to_host_async()
        self._pending = (rnd, tree)
        self.device_bytes = max(self.device_bytes, tree_bytes(tree))

    def settle(self) -> None:
        if self._pending is None:
            return
        rnd, tree = self._pending
        self._pending = None
        self.trees[rnd] = jax.tree.map(np.asarray, tree)
        while len(self.trees) > self.size:
            del self.trees[min(self.trees)]
        self.host_bytes = max(self.host_bytes, sum(map(tree_bytes, self.trees.values())))


class Probe:
    """``capture_rounds``: the rounds whose norms to keep."""

    def __init__(self, task, *, trace: bool, capture_rounds: set):
        self.task = task
        self.trace = trace
        self.capture_rounds = capture_rounds
        shapes = jax.eval_shape(task.init_fn, jax.random.PRNGKey(0))
        self.n_leaves = len(jax.tree.leaves(shapes))
        self.tree_bytes = tree_bytes(shapes)
        self.counting = False  # counters advance inside a measured window only
        self.counters: Dict[str, float] = defaultdict(float)
        self.run_id: Any = None
        self._last_round_key = None
        self.round_starts: List[tuple] = []  # (run_id, t)
        self.run_ends: Dict[Any, float] = {}
        # id(ServerConfig) -> one record per round that reached transport
        self.flows: Dict[int, List[Dict[str, Any]]] = defaultdict(list)
        # id(ServerConfig) -> round -> per-leaf norms against the point's start
        self.captured: Dict[int, Dict[int, Any]] = defaultdict(dict)
        self.capture_keys: set = set()  # id(ServerConfig) whose rounds to capture
        self.buffers: Dict[int, TreeBuffer] = {}  # id(ServerConfig) -> its trees
        self.on_begin_round: Optional[Callable] = None
        self.deadline: Optional[float] = None  # the window closes at its first round after this
        self.closed = False
        self._saved: List[tuple] = []
        self._listening = False

    # -- spans and counters ---------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, timed: Optional[str] = None):
        t0 = time.perf_counter()
        if self.trace:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        else:
            yield
        if timed and self.counting:
            self.counters[timed] += time.perf_counter() - t0

    def count(self, key: str, n: float) -> None:
        if self.counting:
            self.counters[key] += n

    def _on_compile(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count("compiles", 1)

    def fit_dispatches(self) -> int:
        return len(self.task.fit_rows.runner.dispatch_widths)

    # -- what the references compare ---------------------------------------------
    def capture(self, srv, rnd: int) -> None:
        key = id(srv.config)
        if rnd in self.capture_rounds:
            self.captured[key][rnd] = check.init_norms(
                self.task.init_fn, srv.global_params, jax.random.PRNGKey(srv.config.seed))
        if key in self.buffers:
            self.buffers[key].add(rnd, srv.global_params)

    def kept(self) -> Dict[str, int]:
        """The most bytes of whole trees the probe held at once, on the
        device and on the host, and the bytes of one tree."""
        for buf in self.buffers.values():
            buf.settle()
        return {"device": max((b.device_bytes for b in self.buffers.values()), default=0),
                "host": max((b.host_bytes for b in self.buffers.values()), default=0),
                "tree": self.tree_bytes}

    # -- rounds ---------------------------------------------------------------
    def mark_round(self, rnd: int) -> None:
        """Stamp a round's start, or close the window at the first round
        that would start after the deadline."""
        key = (self.run_id, rnd)
        if key != self._last_round_key:
            self._last_round_key = key
            now = time.perf_counter()
            if self.deadline is not None and now >= self.deadline:
                self.closed = True
            else:
                self.round_starts.append((self.run_id, now))

    def end_run(self) -> None:
        self.run_ends[self.run_id] = time.perf_counter()

    def round_times(self, runs) -> List[float]:
        """Wall seconds of every round of the given runs: from its start to
        the next round's start, the last one to its run's end."""
        out = []
        for run in runs:
            ts = [t for r, t in self.round_starts if r == run] + [self.run_ends[run]]
            out += list(np.diff(ts))
        return out

    # -- installation -----------------------------------------------------------
    def _patch(self, obj, attr: str, make: Callable) -> None:
        orig = getattr(obj, attr)
        if orig is None:  # a task without this path: nothing to wrap
            return
        self._saved.append((obj, attr, orig))
        wrapped = make(orig)
        for a in ("runner",):
            if hasattr(orig, a):
                setattr(wrapped, a, getattr(orig, a))
        setattr(obj, attr, wrapped)

    def install(self) -> None:
        import repro.core.grid as grid
        import repro.kernels.ops as kops
        import repro.transport.plane as tplane
        from repro.core.population import Population
        from repro.core.server import FederatedServer
        from repro.core.strategy import Strategy

        probe = self

        # -- the round seam, in every run --------------------------------------
        def select_cohort(orig):
            def f(srv, rnd):
                probe.mark_round(rnd)
                if probe.closed:  # the window has closed: end this engine run
                    srv.terminated = True
                    return None
                with probe.span("select", timed="select_s"):
                    return orig(srv, rnd)
            return f

        def begin_round(orig):
            def f(srv, rnd):
                if probe.on_begin_round is not None:
                    probe.on_begin_round(srv, rnd)
                probe.mark_round(rnd)
                if probe.closed:
                    srv.terminated = True
                    return None
                with probe.span("begin_round"):
                    return orig(srv, rnd)
            return f

        def finish_transport(orig):
            def f(srv, pending, completed, times, *a, **kw):
                with probe.span("finish_transport"):
                    job = orig(srv, pending, completed, times, *a, **kw)
                probe.flows[id(srv.config)].append({
                    "run": probe.run_id, "rnd": pending.rnd,
                    "ids": [c.client_id for c in pending.cohort],
                    "connected": np.array(pending.connected, bool),
                    "completed": np.array(completed, bool),
                    "times": np.array(times, float),
                    "committed": None if job is None else [c.client_id for c in job.clients],
                })
                return job
            return f

        def finish_round(orig):
            def f(srv, job, *a, **kw):
                with probe.span("finish_round"):
                    out = orig(srv, job, *a, **kw)
                if id(srv.config) in probe.capture_keys:
                    probe.capture(srv, job.rnd)
                return out
            return f

        self._patch(FederatedServer, "select_cohort", select_cohort)
        self._patch(FederatedServer, "begin_round", begin_round)
        self._patch(FederatedServer, "finish_transport", finish_transport)
        self._patch(FederatedServer, "finish_round", finish_round)
        if not self.trace:
            return

        # -- spans and counters, in a traced run --------------------------------
        def fit_rows(orig):
            def f(anchors, rows, steps, *a, **kw):
                probe.count("fit_row_steps", len(rows) * steps)
                with probe.span("fit_rows"):
                    return orig(anchors, rows, steps, *a, **kw)
            return f

        def batched_local_fit(orig):
            def f(params, clients, steps, *a, **kw):
                probe.count("fit_row_steps", len(clients) * steps)
                with probe.span("fit_rows"):
                    return orig(params, clients, steps, *a, **kw)
            return f

        def evaluate(orig):
            def f(params, data):
                probe.count("eval_examples", examples(data))
                with probe.span("evaluate"):
                    return orig(params, data)
            return f

        def fedavg_reduce(orig):
            def f(stacked, weights, **kw):
                from harness import flops

                probe.count("fedavg_reduce_bytes", flops.fedavg_reduce_bytes(
                    [l.shape for l in jax.tree.leaves(stacked)]))
                return orig(stacked, weights, **kw)
            return f

        def spanned(name):
            def make(orig):
                def f(*a, **kw):
                    with probe.span(name):
                        return orig(*a, **kw)
                return f
            return make

        self._patch(self.task, "fit_rows", fit_rows)
        self._patch(self.task, "batched_local_fit", batched_local_fit)
        self._patch(self.task, "evaluate", evaluate)
        self._patch(kops, "fedavg_reduce", fedavg_reduce)
        self._patch(Strategy, "aggregate_stacked", spanned("aggregate"))
        self._patch(tplane, "sim_grid_round_device", spanned("transport"))
        self._patch(FederatedServer, "__init__", spanned("server_init"))
        self._patch(FederatedServer, "_divergence_cause", spanned("divergence_check"))
        self._patch(Population, "client", spanned("materialize_client"))
        self._patch(self.task, "plan_fit", spanned("plan_fit"))
        self._patch(self.task, "plan_digest", spanned("plan_digest"))
        self._patch(grid, "_gather_rows", spanned("gather_rows"))
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        self._listening = True

    def uninstall(self) -> None:
        for obj, attr, orig in reversed(self._saved):
            setattr(obj, attr, orig)
        self._saved.clear()
        if self._listening:
            jax.monitoring.unregister_event_duration_listener(self._on_compile)
            self._listening = False
