"""Plain reference for the transport outcomes: the chance that one client's
round exchange completes within the round deadline, by Monte Carlo over
the exchange the configurations describe, in numpy alone and imported
from nothing in the program.

One client's exchange in a synchronous round:

- a client not connected from its last round first connects: SYN attempt
  k is sent at ``k * syn_rto`` (k = 0 .. ``tcp_syn_retries``) and succeeds
  when neither the SYN nor its answer is lost and its round trip lands by
  the handshake budget ``(tcp_syn_retries + 1) * syn_rto``;
- it downloads the model, trains for ``local_steps * base_step_cost``
  seconds with the connection idle, and uploads its update;
- a transfer sends windows of ``min(cwnd, window, queue limit, link rate x
  rtt)`` segments, each segment lost with the link's loss; a window with
  losses halves ``cwnd`` (floor 2) and, with SACK, holds its delivered
  segments in the reorder buffer, which fails the transfer past 48 x
  ``tcp_rmem``; a loss-free window empties the buffer and grows ``cwnd``
  (doubling below half the window, then by one); a window lost whole
  waits out the retransmit timer, which doubles (to ``max_rto``) while
  each retransmission is lost in turn, and the connection dies at
  ``tcp_retries2`` timeouts in a row;
- an idle connection outlives the training window unless that window is
  longer than the middlebox timeout, where the reaped connection is found
  on send (a stall of the timer's first six doublings, at most 60 s) and
  connects again;
- the exchange counts as delivered when every step succeeded and it ended
  within the round deadline.

Every round trip is ``max(2 * delay + N(0, jitter) + N(0, jitter), 1e-5)``
seconds. Keepalive probing during the idle window (``tcp_keepalive_time``
shorter than the window), retries and session resumption are not
modelled; a flow that needs them raises.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

LINK_KEYS = ("delay", "jitter", "loss", "rate_mbps", "queue_limit", "middlebox_timeout")
TCP_KEYS = ("tcp_syn_retries", "syn_rto", "tcp_keepalive_time", "tcp_retries2", "tcp_rmem",
            "tcp_wmem", "tcp_sack", "tcp_window_scaling", "initial_rto", "max_rto", "mss",
            "profile")
MAX_WINDOWS = 200_000  # a transfer that needs more windows fails


def _rtt(rng, link, n):
    j = rng.normal(0.0, link["jitter"], n) + rng.normal(0.0, link["jitter"], n)
    return np.maximum(2.0 * link["delay"] + j, 1e-5)


def _handshake(rng, link, tcp, t, ok):
    """Connect the flows in ``ok``; returns (ok, t)."""
    budget = (tcp["tcp_syn_retries"] + 1) * tcp["syn_rto"]
    todo = ok.copy()
    done_at = np.full(t.shape, np.nan)
    for k in range(tcp["tcp_syn_retries"] + 1):
        n = int(todo.sum())
        if n == 0:
            break
        t_send = k * tcp["syn_rto"]
        rtt = _rtt(rng, link, n)
        through = (rng.random(n) >= link["loss"]) & (rng.random(n) >= link["loss"])
        hit = through & (t_send + rtt <= budget)
        idx = np.flatnonzero(todo)[hit]
        done_at[idx] = t_send + rtt[hit]
        todo[idx] = False
    connected = ok & ~np.isnan(done_at)
    return connected, t + np.where(connected, done_at, budget)


def _transfer(rng, link, tcp, nbytes, t, ok, deadline):
    """Move ``nbytes`` over each flow in ``ok``; returns (ok, t). A flow
    whose clock passes the deadline stops: it can no longer deliver."""
    mss = tcp["mss"]
    window = min(tcp["tcp_rmem"], tcp["tcp_wmem"])
    if not tcp["tcp_window_scaling"]:
        window = min(window, 65535)
    wnd_max = max(window // mss, 2)
    segs = max(1, math.ceil(nbytes / mss))
    p = link["loss"]
    ok, t = ok.copy(), t.copy()
    idx = np.flatnonzero(ok)
    n = idx.size
    tt = t[idx]
    cwnd = np.full(n, 10.0)
    acked = np.zeros(n, np.int64)
    pending = np.zeros(n, np.int64)
    rto = np.full(n, float(tcp["initial_rto"]))
    reorder = np.zeros(n)
    live = np.ones(n, bool)
    fine = np.ones(n, bool)
    for _ in range(MAX_WINDOWS):
        a = np.flatnonzero(live)
        if a.size == 0:
            break
        rtt = _rtt(rng, link, a.size)
        w = np.minimum(np.minimum(cwnd[a], wnd_max), link["queue_limit"])
        if link["rate_mbps"] > 0:
            cap = np.maximum(np.floor(link["rate_mbps"] * 1e6 / 8.0 * rtt / mss), 1)
            w = np.minimum(w, cap)
        w = np.floor(w).astype(np.int64)
        w = np.minimum(np.maximum(w, 1), segs - acked[a] + pending[a])
        lost = rng.binomial(w, p) if p > 0 else np.zeros_like(w)
        got = w - lost
        tt[a] += rtt
        # a window lost whole: the retransmit timer, doubling while each
        # retransmission is lost too
        whole = got == 0
        if whole.any():
            b = a[whole]
            extra = np.minimum(rng.geometric(1.0 - p, b.size) - 1, max(tcp["tcp_retries2"] - 1, 0))
            r = rto[b]
            tt[b] += r
            for i in range(1, int(extra.max(initial=0)) + 1):
                r = np.where(extra >= i, np.minimum(r * 2, tcp["max_rto"]), r)
                tt[b] += np.where(extra >= i, r, 0.0)
            dead = 1 + extra >= tcp["tcp_retries2"]
            fine[b[dead]] = False
            live[b[dead]] = False
            cwnd[b] = 10.0
            rto[b] = np.minimum(r * 2, tcp["max_rto"])
        # a window that got through, in part or whole
        c = a[~whole]
        lc, gc = lost[~whole], got[~whole]
        rto[c] = tcp["initial_rto"]
        holes = (lc > 0) & bool(tcp["tcp_sack"])
        h = c[holes]
        reorder[h] += gc[holes] * mss
        burst = reorder[h] > tcp["tcp_rmem"] * 48
        fine[h[burst]] = False
        live[h[burst]] = False
        cwnd[h] = np.maximum(cwnd[h] / 2.0, 2.0)
        pending[h] = lc[holes]
        g = c[~holes]
        reorder[g] = 0.0
        pending[g] = 0
        cwnd[g] = np.where(cwnd[g] >= wnd_max / 2, cwnd[g] + 1.0, cwnd[g] * 2.0)
        keep = ~burst
        acked[h[keep]] += gc[holes][keep]
        acked[g] += gc[~holes]
        live &= acked < segs
        late = live & (tt > deadline)
        fine[late] = False
        live[late] = False
    else:
        fine[live] = False
    ok[idx] = fine
    t[idx] = tt
    return ok, t


def completion(link: Dict, tcp: Dict, *, down_bytes: int, up_bytes: int, idle_s: float,
               deadline: float, connected: bool, n: int, seed: int) -> Dict[str, float]:
    """Over ``n`` simulated exchanges: ``p``, the share that complete
    within the deadline (the chance of one, within its Monte Carlo error),
    and the mean and variance of the completed ones' durations (NaN where
    none completes)."""
    if tcp["profile"] not in ("tcp_default", "tcp_tuned"):
        raise ValueError(f"no flow reference for the {tcp['profile']!r} profile")
    if tcp["tcp_keepalive_time"] < idle_s:
        raise ValueError("keepalive probing during the idle window is not modelled")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    ok = np.ones(n, bool)
    t = np.zeros(n)
    if not connected:
        ok, t = _handshake(rng, link, tcp, t, ok)
    ok, t = _transfer(rng, link, tcp, down_bytes, t, ok, deadline)
    t = t + idle_s
    if idle_s > link["middlebox_timeout"]:
        stall = min(sum(min(tcp["initial_rto"] * 2 ** i, tcp["max_rto"]) for i in range(6)), 60.0)
        ok, t = _handshake(rng, link, tcp, t + stall, ok)
    ok, t = _transfer(rng, link, tcp, up_bytes, t, ok, deadline)
    done = ok & (t <= deadline)
    times = t[done]
    return {"p": float(np.mean(done)),
            "mean_s": float(times.mean()) if times.size else float("nan"),
            "var_s": float(times.var()) if times.size else float("nan")}
