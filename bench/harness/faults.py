"""Faults planted under the timed path, to show that ``correct`` catches
them: the comparison's tests run each one a cell lists in its limits
file, and ``calibrate.py --fault`` reads it on the chip.

- ``state_unchanged``: aggregation leaves the global params as they were
  (a round that returns its state unchanged);
- ``half_batch``: FedAvg over the first half of the delivered updates
  only, the mean taken over the rest;
- ``lost_delivery``: the transport outcome of each round's first cohort
  member turned to a failure where the engine receives it (an answer
  altered where it is received);
- ``ignore_loss``: the device transport plane runs every link as if it
  lost no packet (an answer altered where it is produced);
- ``deliver_all``: every transport outcome reports the flow delivered,
  failed flows at 1 s (an answer altered where it is produced).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np


def _state_unchanged(orig):
    def apply(self, global_params, agg_delta, step):
        return global_params
    return apply


def _half_batch(orig):
    def aggregate_stacked(self, global_params, stacked, weights, step):
        keep = max(1, len(weights) // 2)
        half = jax.tree.map(lambda l: l[:keep], stacked)
        return orig(self, global_params, half, list(weights)[:keep], step)
    return aggregate_stacked


def _lost_delivery(orig):
    def finish_transport(self, pending, completed, *a, **kw):
        completed = np.array(completed, bool)
        completed[:1] = False
        return orig(self, pending, completed, *a, **kw)
    return finish_transport


def _lossless(orig):
    def from_arrays(cls, la):
        lp = orig.__func__(cls, la)
        return lp._replace(loss=jnp.zeros_like(lp.loss), surv2=jnp.ones_like(lp.surv2))
    return classmethod(from_arrays)


def _all_delivered(outcome):
    completed, times, *rest = outcome
    completed = np.asarray(completed, bool)
    return (np.ones_like(completed), np.where(completed, np.asarray(times, float), 1.0), *rest)


def _deliver_all_server(orig):
    def run_transport(self, pending):
        return _all_delivered(orig(self, pending))
    return run_transport


def _deliver_all_grid(orig):
    def plane_transport(*a, **kw):
        return [_all_delivered(o) for o in orig(*a, **kw)]
    return plane_transport


FAULTS = {
    "state_unchanged": [("strategy", "_apply", _state_unchanged)],
    "half_batch": [("strategy", "aggregate_stacked", _half_batch)],
    "lost_delivery": [("server", "finish_transport", _lost_delivery)],
    "ignore_loss": [("link_plane", "from_arrays", _lossless)],
    "deliver_all": [("server", "run_transport", _deliver_all_server),
                    ("grid", "_plane_transport", _deliver_all_grid)],
}


@contextlib.contextmanager
def planted(name: str):
    import repro.core.grid as grid
    from repro.core.server import FederatedServer
    from repro.core.strategy import Strategy
    from repro.transport.plane import LinkPlane

    where = {"strategy": Strategy, "server": FederatedServer, "grid": grid,
             "link_plane": LinkPlane}
    saved = []
    try:
        for target, attr, make in FAULTS[name]:
            obj = where[target]
            orig = vars(obj)[attr]
            saved.append((obj, attr, orig))
            setattr(obj, attr, make(orig))
        yield
    finally:
        for obj, attr, orig in reversed(saved):
            setattr(obj, attr, orig)
