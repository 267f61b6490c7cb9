"""The benchmark's plain reference for a typed fleet: a numpy slot loop
with per-level running and switching costs.

A typed fleet is d server types, each with ``n_servers`` identical
machines, a running cost ``P`` per slot and switching costs ``beta_on`` and
``beta_off`` (Albers and Quedenfeld, arXiv:2107.14672).  The dispatcher
gives base load to the type cheapest to run: the types are stacked in
ascending ``P`` (ties in the order given) and level ``l`` of the stack is
busy in slot ``t`` iff ``a[t] > l``.  The policies are the typed fleet's
window-free ones: no peek; an idle level that is on is turned off once its
idle run has outlasted its wait, which is its type's break-even interval
Delta = (beta_on + beta_off) / P (AQ-det) or, for AQ-rand, a draw from a
``(T, N)`` table whose entry ``[t, l]`` is consumed iff level ``l`` becomes
newly idle in slot ``t``.  Delta is computed in float32, as the program
computes it.  Otherwise the semantics are :func:`bench.reference.slot_loop`'s:
``x(0) = a(0)`` and every idle level forced off after the last slot.  It
imports nothing of the program.

``acc_dtype`` is the precision of the idle clocks, the waits and the cost
accumulators: ``float64`` is the reference, ``bfloat16`` the control that
the comparison has to reject.
"""
from __future__ import annotations

import numpy as np

from bench.reference import _bf16_sum, _rounding


def per_level(groups):
    """(P, beta_on, beta_off, Delta, sizes) of a list of groups (dicts with
    ``n_servers``, ``P``, ``beta_on``, ``beta_off``), stacked in dispatch
    order: float32 ``(N,)`` arrays and the group sizes in that order."""
    order = sorted(groups, key=lambda g: float(g["P"]))
    sizes = [int(g["n_servers"]) for g in order]

    def field(k):
        return np.concatenate([np.full(n, g[k], np.float32) for n, g in zip(sizes, order)])

    P, b_on, b_off = field("P"), field("beta_on"), field("beta_off")
    return P, b_on, b_off, (b_on + b_off) / P, sizes


def slot_loop(a, groups, *, waits=None, final_off=True, acc_dtype="float64"):
    """Run the typed fleet ``groups`` over demand ``a``.

    ``a``: (T,) int demand, at most the fleet's size; ``waits``: (T, N)
    drawn thresholds (AQ-rand), or None for the deterministic timers
    (AQ-det).  Returns a dict of ``x`` (1, T) int64, ``level_cost`` (1, N),
    ``cost`` (1,) and ``group_cost`` (1, d), each with the leading axis of
    the one window a window-free policy has.

    Levels below ``min(a[t-1], a[t])`` were busy in the last slot and are
    busy now, so each slot updates only the band from there to one past the
    highest level that is on.
    """
    P, b_on, b_off, delta, sizes = per_level(groups)
    n_levels = P.shape[0]
    a = np.asarray(a, np.int64)
    T = a.shape[0]
    if a.min() < 0 or a.max() > n_levels:
        raise ValueError("demand must lie in [0, n_levels]")
    dt, rnd = _rounding(acc_dtype)
    levels = np.arange(n_levels)
    on = np.zeros(n_levels, bool)
    on[:a[0]] = True                                     # x(0) = a(0)
    r = np.zeros(n_levels, dt)
    wait = rnd(delta.astype(dt))
    idle_run = np.zeros(n_levels, dt)                    # on-and-idle slots
    ups = np.zeros(n_levels, dt)
    downs = np.zeros(n_levels, dt)
    x = np.zeros(T, np.int64)
    top = int(a[0])                                      # no level >= top is on
    for t in range(T):
        lo = int(min(a[t], a[t - 1] if t else a[0]))
        hi = max(top, int(a[t]))
        busy = a[t] > levels[lo:hi]
        o = on[lo:hi]
        rr = r[lo:hi]
        ups[lo:hi] = rnd(ups[lo:hi] + (busy & ~o))
        o |= busy
        rr[busy] = 0
        idle = o & ~busy
        if waits is not None:
            new = idle & (rr == 0)
            wait[lo:hi] = np.where(new, rnd(waits[t, lo:hi]), wait[lo:hi])
        rr = rnd(np.where(idle, rr + 1, rr))
        off = idle & (rnd(rr - 1) >= wait[lo:hi])
        downs[lo:hi] = rnd(downs[lo:hi] + off)
        o &= ~off
        r[lo:hi] = np.where(off, 0, rr)
        idle_run[lo:hi] = rnd(idle_run[lo:hi] + (o & ~busy))
        x[t] = lo + o.sum()
        top = lo + int(np.flatnonzero(o).max(initial=-1)) + 1
    if final_off:
        downs = rnd(downs + (on & ~(a[-1] > levels)))
    # busy slots of level l: how many slots have a[t] > l
    busy_slots = np.cumsum(np.bincount(a, minlength=n_levels + 1)[::-1])[::-1][1:]
    run = rnd(idle_run + busy_slots[:n_levels])
    level_cost = rnd(rnd(rnd(run * P) + rnd(ups * b_on)) + rnd(downs * b_off))[None]
    bounds = np.cumsum([0] + sizes)
    parts = [level_cost[:, s:e] for s, e in zip(bounds[:-1], bounds[1:])]
    if acc_dtype == "bfloat16":
        cost = _bf16_sum(level_cost, rnd)
        group_cost = np.stack([_bf16_sum(p, rnd) for p in parts], axis=1)
    else:
        cost = level_cost.sum(axis=1)
        group_cost = np.stack([p.sum(axis=1) for p in parts], axis=1)
    return {"x": x[None], "level_cost": level_cost, "cost": cost, "group_cost": group_cost}
