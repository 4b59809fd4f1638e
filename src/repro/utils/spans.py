"""Named spans of the FL round engine, on the profiler's clock.

``span(name, **counts)`` is ``jax.profiler.TraceAnnotation("fl." + name)``
with ``counts`` as its metadata: cheap ints only (``len(...)``,
``.nbytes``), which a trace reader gets back as the event's stats. A span
records only while a profiler session runs (``jax.profiler.trace``); with
none it costs about a microsecond, so there is no switch. Spans never sit
inside a jitted function, where they would fire only while tracing.
``to_host(x, site)`` is the round path's one device-to-host read.

Open a run inside ``jax.profiler.trace(logdir)`` and read these in Perfetto
(metadata in brackets):

- ``fl.round`` [round]: one engine round: the body of ``run_fl_grid``'s round, one iteration of ``FederatedServer.run``.
- ``fl.select`` [cohort]: ``FederatedServer.select_cohort``: liveness, cohort draw, links.
- ``fl.shard_build`` [examples]: ``Population.client`` building one drawn client's shard.
- ``fl.transport`` [rows]: transport sampling: ``FederatedServer.run_transport``, the grid's shared transport plane.
- ``fl.finish_transport`` [rows]: ``FederatedServer.finish_transport``: deliveries, quorum, the FitJob.
- ``fl.plan`` [rows]: batch plans (``plan_fit``) and the grid's coalescing row table.
- ``fl.fit.batches`` [rows, steps]: the host gather and stack of one fit block's examples.
- ``fl.fit.h2d`` [bytes]: the copy of that block's examples to the device.
- ``fl.fit.anchors`` [anchors]: the host build of one block's anchor table (its distinct anchors), row index and prox arrays.
- ``fl.fit.dispatch`` [rows, steps]: the fit program's call for one block.
- ``fl.gather_rows`` [rows]: the grid's gather of one point's rows from the fit planes.
- ``fl.divergence``: ``FederatedServer._divergence_cause``, the quarantine check.
- ``fl.aggregate`` [rows]: ``Strategy.aggregate_stacked``.
- ``fl.finish_round`` [rows]: ``FederatedServer.finish_round``: bookkeeping, aggregation, eval.
- ``fl.evaluate`` [examples]: the task's ``evaluate``.
- ``fl.sync.<site>`` [bytes]: ``to_host``, the host waiting on the device; sites ``fit_metrics``, ``transport``, ``divergence``, ``eval``.
"""

from __future__ import annotations

from typing import Any

import jax

PREFIX = "fl."


def span(name: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """The span ``fl.<name>``; use it as a context manager. Counts known
    only at the end go in through the span's ``set_metadata``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)


def to_host(x: Any, site: str) -> Any:
    """``jax.device_get(x)`` under the span ``fl.sync.<site>``: the one way
    the round path reads device values, so each read is timed and counted."""
    nbytes = sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(x))
    with span("sync." + site, bytes=int(nbytes)):
        return jax.device_get(x)
