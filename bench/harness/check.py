"""The comparison that decides ``correct``.

Training: a sample of points drawn from the seed is followed by the plain
FL reference (``configs/<reference>``) through three rounds, on the same
cohorts, the same committed clients and the same batch plans: from the
benchmark-made weights at round 0, and (population cells) from the
program's params before a window round drawn from the seed. The numbers,
each worst over the sample:

- ``cohort_mismatches``: rounds whose selected client ids differ from the
  reference's replay of the selection draw (exact: limit 0);
- ``loss_gap``: |program eval loss - reference eval loss| / reference, over
  the committed rounds followed;
- ``loss_gap_nats``: the same gap unscaled, in nats. Where the rounds
  followed lie late in training, the falling loss inflates the relative
  gap of the same precision error several times over; the gap in nats
  stays level (a cell compares the one its limits file names);
- ``update_gap``: the first committed round's change of the global params,
  by leaf: |program norm - reference norm| over the larger of the
  reference's norm of that leaf and of the median leaf, worst leaf;
- ``change_gap``: the same for the change over the rounds followed.

Leaves whose reference update is under a thousandth of the median leaf's
are left out of both norm gaps (their change is round-off alone). Both
sides are compared by their per-leaf norms against the point's start
(``leaf_norms``), taken where each tree is made: the program's as the probe
captures a round, the reference's as ``replay_norms`` reads its rounds. So
what the check keeps does not grow with the payload's rounds or points:
whole trees only where a population window sample starts from the
program's params (``probe.TreeBuffer``).

Transport: every round's outcomes as the engine received them, against
the flow reference (``configs/<flow_reference>``), which gives for each
link, TCP preset and connection state the chance that an exchange
completes within the deadline, and the mean and variance of its duration:

- ``delivery_z``: per group of flows (one sweep point, or a population
  run), |delivered - expected| over the square root of the binomial
  variance plus the reference's Monte Carlo variance plus 1, worst group;
- ``arrival_z``: per group, |mean duration of the delivered flows -
  the reference's mean for the same mix of connection states| over the
  standard error of the latter (the spread of that many durations and the
  reference's Monte Carlo error, with a floor of 1% of the mean), worst
  group (stochastic transport only: the analytic model's durations are a
  closed form of its own);
- ``commit_errors``: rounds whose committed clients are not those the
  configuration's rule gives: when at least the quorum of flows arrived
  within the deadline, the first ``goal`` arrivals (all of them where the
  round has no goal), else none (exact: limit 0).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("cohort_mismatches", "loss_gap", "loss_gap_nats", "update_gap", "change_gap")  # FL reference
TINY_LEAF = 1e-3
TIME_FLOOR = 0.01  # durations agree to 1% where neither side varies


def _tree_norms(tree, base):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))
                      for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(base))])


leaf_norms = jax.jit(_tree_norms)  # per leaf, ||tree - base||, on the device


@functools.partial(jax.jit, static_argnums=0)
def init_norms(init_fn, tree, key):
    """``leaf_norms`` against ``init_fn(key)``, made inside the reduction
    rather than held."""
    return _tree_norms(tree, init_fn(key))


def replay_norms(replayed: List[Dict], base) -> List[Dict]:
    """A reference replay's rounds with each round's per-leaf ``norms``
    against ``base``, the replay's start: the replay's own where it gives
    them, else those of its ``params``, which are not kept."""
    return [{"cohort": x["cohort"], "loss": x["loss"], "norms": np.asarray(
        x["norms"] if "norms" in x else leaf_norms(x["params"], base), np.float64)}
        for x in replayed]


def norm_gap(p: np.ndarray, r: np.ndarray, keep: np.ndarray) -> float:
    denom = np.maximum(r, np.median(r))
    if not np.all(denom[keep] > 0):
        return float("inf")
    return float(np.max(np.abs(p - r)[keep] / denom[keep]))


def compare(prog: Dict, ref: List[Dict], committed: List[bool]) -> Dict[str, float]:
    """Readings of one point. ``prog`` holds per round followed
    ``cohorts[i]``, ``losses[i]`` (None where the round failed) and
    ``norms[i]`` (per-leaf norms of the global params after it against the
    params before the first round followed); ``ref`` is ``replay_norms``
    over the reference's (or a control's) replay of the same rounds."""
    rounds = len(ref)
    mism = sum(1 for r in range(rounds) if list(prog["cohorts"][r]) != ref[r]["cohort"])
    nats = [abs(prog["losses"][r] - ref[r]["loss"]) for r in range(rounds) if committed[r]]
    gaps = [abs(prog["losses"][r] - ref[r]["loss"]) / abs(ref[r]["loss"])
            for r in range(rounds) if committed[r]]
    first = next((r for r in range(rounds) if committed[r]), None)
    if first is None:
        return dict({k: float("inf") for k in NAMES}, cohort_mismatches=float(mism))
    r_first = ref[first]["norms"]
    keep = r_first >= TINY_LEAF * np.median(r_first)
    last = rounds - 1
    return {
        "cohort_mismatches": float(mism),
        "loss_gap": float(max(gaps)),
        "loss_gap_nats": float(max(nats)),
        "update_gap": norm_gap(prog["norms"][first], r_first, keep),
        "change_gap": norm_gap(prog["norms"][last], ref[last]["norms"], keep),
    }


def worst(readings: List[Dict[str, float]]) -> Dict[str, float]:
    return {k: max(r[k] for r in readings) for k in NAMES}


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict]:
    """Each number the cell's limits name, beside its limit; a number the
    run could not read is infinite."""
    return {k: {"value": float(values.get(k, float("inf"))), "limit": limits[k]} for k in limits}


def passes(checks: Dict[str, Dict]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())


def describe(checks: Dict[str, Dict]) -> List[str]:
    return [f"{k}: {c['value']!r} (limit {c['limit']!r})" for k, c in checks.items()]


def program_point(history, norms: Dict[int, object], rounds: List[int],
                  n_leaves: int) -> Optional[Dict]:
    """The program's side of one point over ``rounds``, from its History
    and the per-leaf norms the probe kept, against the params before
    ``rounds[0]``: a failed round's are those of the round before, and
    before the first round followed they are zero."""
    recs = {r.round_idx: r for r in history.rounds}
    if any(r not in recs for r in rounds):
        return None
    losses = {m["round"]: m["loss"] for m in history.eval_metrics}
    out, prev = [], np.zeros(n_leaves)
    for r in rounds:
        if r in norms:
            prev = np.asarray(norms[r], np.float64)
        out.append(prev)
    return {
        "cohorts": [recs[r].selected_ids for r in rounds],
        "losses": [losses.get(r) for r in rounds],
        "norms": out,
        "committed": [not recs[r].failed_round for r in rounds],
    }


def commit_errors(records: List[Dict], quorum: int, goal: Optional[int], deadline: float) -> int:
    """Rounds whose committed clients are not the first ``goal`` arrivals
    within the deadline (all of them where ``goal`` is None, ties in
    cohort order), or that commit with fewer than ``quorum`` arrivals."""
    bad = 0
    for rec in records:
        arrived = np.flatnonzero(rec["completed"] & (rec["times"] <= deadline))
        order = sorted(arrived, key=lambda j: (rec["times"][j], j))
        want = None if len(order) < quorum else sorted(
            rec["ids"][j] for j in order[:goal or len(order)])
        got = None if rec["committed"] is None else sorted(rec["committed"])
        bad += want != got
    return bad


def delivery(records: List[Dict], ref, samples: int, deadline: float, times: bool) -> Dict:
    """``delivery_z`` and (with ``times``) ``arrival_z`` of one group of
    flows; ``ref(connected)`` is the flow reference's reading over
    ``samples`` simulated exchanges."""
    conn = np.concatenate([r["connected"] for r in records])
    done = np.concatenate([r["completed"] & (r["times"] <= deadline) for r in records])
    dur = np.concatenate([r["times"] for r in records])
    k_all = int(done.sum())
    expected, var, mean, var_mean = 0.0, 1.0, 0.0, 0.0
    for c in (False, True):
        sel = conn == c
        n, k = int(sel.sum()), int(done[sel].sum())
        if n == 0:
            continue
        q = ref(c)
        expected += n * q["p"]
        var += n * q["p"] * (1 - q["p"]) + n * n * q["p"] * (1 - q["p"]) / samples
        if k:  # the reference's mean duration for this mix, and its variance
            w = k / k_all
            mean += w * q["mean_s"]
            var_mean += w * w * q["var_s"] * (1 / k + 1 / (samples * max(q["p"], 1 / samples)))
    out = {"delivery_z": abs(k_all - expected) / np.sqrt(var)}
    if times and k_all:
        sd = np.sqrt(var_mean + (TIME_FLOOR * mean) ** 2)
        out["arrival_z"] = (abs(float(dur[done].mean()) - mean) / sd
                            if np.isfinite(mean) and sd > 0 else float("inf"))
    return out
