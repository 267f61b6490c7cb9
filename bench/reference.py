"""The benchmark's plain reference: a numpy slot loop, vectorized over levels.

It has the semantics of the program's ``fluid_scan`` (paper Secs. IV-C and
V): level ``l`` is busy in slot ``t`` iff ``a[t] > l``; the dispatcher turns
every busy level on; an idle level that is on is turned off once its idle
run has outlasted its wait and, for the peeking policies, no busy slot is
seen in the prediction window; the schedule starts at ``x(0) = a(0)`` and
every idle level is forced off after the last slot.  It imports nothing of
the program.

A policy is a rule in a file of its own, ``bench/policies/<policy>.py``:
``horizon(windows, delta)`` and ``static_wait(windows, delta)`` give each
window's peek horizon and deterministic wait, and a randomized policy's
``waits(key, n_slots, n_levels, windows, delta)`` its table of wait draws.

Two departures from ``fluid_scan``, both for use at full width:

- every window of a sweep and every level advance together as ``(W, N)``
  arrays, one slot at a time;
- the randomized policies take their wait thresholds as input, a
  ``(W, T, N)`` table whose entry ``[w, t, l]`` is consumed iff level ``l``
  becomes newly idle in slot ``t``.  :func:`wait_tables` makes it from a
  PRNG key with the same split and transform as the engine.

``acc_dtype`` is the precision of the idle clocks, the waits and the cost
accumulators.  ``float64`` is the reference; ``bfloat16`` is the control
that the comparison in :mod:`bench.compare` has to reject.
"""
from __future__ import annotations

import math

import numpy as np

from bench import discover

E = math.e


def _rounding(acc_dtype):
    """(storage dtype, rounding function) of a precision name: bfloat16 is
    emulated as float32 storage rounded to bfloat16 after every operation."""
    if acc_dtype == "bfloat16":
        import ml_dtypes

        bf16 = np.dtype(ml_dtypes.bfloat16)
        return np.dtype(np.float32), lambda v: np.asarray(v).astype(bf16).astype(np.float32)
    dt = np.dtype(acc_dtype)
    return dt, lambda v: np.asarray(v, dt)


def policy_rule(policy, root=discover.ROOT):
    """The rule of a policy: its module, or the one its name finds."""
    return discover.module("policies", policy, root) if isinstance(policy, str) else policy


def peek_horizon(windows, delta):
    """Slots a peeking policy sees ahead: w + 1, at most ceil(Delta)."""
    return np.minimum(np.asarray(windows, np.float64) + 1, math.ceil(delta)).astype(np.int64)


def peek_static_wait(windows, delta):
    """A peeking policy's deterministic wait: Delta - w - 1, at least 0."""
    return np.maximum(0.0, delta - np.asarray(windows, np.float64) - 1.0)


def slot_loop(a, n_levels, costs, *, policy, windows=(0,), pred=None,
              waits=None, final_off=True, acc_dtype="float64"):
    """Run ``policy`` over demand ``a`` for each window of ``windows``.

    ``a``: (T,) int demand, at most ``n_levels``; ``costs``: dict with
    ``P``, ``beta_on``, ``beta_off``; ``policy``: a rule module or its
    name; ``pred``: (T,) trace the peek reads (default ``a``); ``waits``:
    (W, T, N) thresholds for the randomized policies; ``final_off=False``
    leaves out the forced turn-off after the last slot (a live stepper's
    trace has not ended).  Returns a dict of ``x`` (W, T) int64 and
    ``level_cost`` (W, N) and ``cost`` (W,).

    Levels below ``min(a[t-1], a[t])`` were busy in the last slot and are
    busy now: nothing of theirs changes but their run, which is counted
    from the demand histogram.  So each slot updates only the band from
    there to one past the highest level that is on.
    """
    rule = policy_rule(policy)
    a = np.asarray(a, np.int64)
    pred = a if pred is None else np.asarray(pred, np.int64)
    T = a.shape[0]
    if a.min() < 0 or a.max() > n_levels:
        raise ValueError("demand must lie in [0, n_levels]")
    W = len(windows)
    dt, rnd = _rounding(acc_dtype)
    P, b_on, b_off = (float(costs[k]) for k in ("P", "beta_on", "beta_off"))
    delta = (b_on + b_off) / P
    horizon = np.asarray(rule.horizon(windows, delta), np.int64)
    m_static = np.asarray(rule.static_wait(windows, delta), np.float64)
    if hasattr(rule, "waits") and waits is None:
        raise ValueError(f"{rule.__name__} is randomized: it needs its wait table")
    h_max = int(horizon.max()) if W else 0
    pred_pad = np.concatenate([pred, np.full(h_max + 1, -1, np.int64)])

    levels = np.arange(n_levels)
    on = np.zeros((W, n_levels), bool)
    on[:, :a[0]] = True                                  # x(0) = a(0)
    r = np.zeros((W, n_levels), dt)
    wait = rnd(np.broadcast_to(m_static[:, None], (W, n_levels)).copy())
    idle_run = np.zeros((W, n_levels), dt)               # on-and-idle slots
    ups = np.zeros((W, n_levels), dt)
    downs = np.zeros((W, n_levels), dt)
    x = np.zeros((W, T), np.int64)
    top = int(a[0])                                      # no level >= top is on
    for t in range(T):
        lo = int(min(a[t], a[t - 1] if t else a[0]))
        hi = max(top, int(a[t]))
        lv = levels[lo:hi]
        busy = a[t] > lv
        o = on[:, lo:hi]
        rr = r[:, lo:hi]
        ups[:, lo:hi] = rnd(ups[:, lo:hi] + (busy & ~o))
        o |= busy
        rr[:, busy] = 0
        idle = o & ~busy
        if waits is not None:
            new = idle & (rr == 0)
            wait[:, lo:hi] = np.where(new, rnd(waits[:, t, lo:hi]), wait[:, lo:hi])
        rr = rnd(np.where(idle, rr + 1, rr))
        off = idle & (rnd(rr - 1) >= wait[:, lo:hi])
        if h_max:
            fut = np.maximum.accumulate(pred_pad[t + 1:t + 1 + h_max])
            seen_max = np.where(horizon > 0, fut[np.maximum(horizon, 1) - 1], -1)
            off &= ~(seen_max[:, None] > lv[None, :])
        downs[:, lo:hi] = rnd(downs[:, lo:hi] + off)
        o &= ~off
        r[:, lo:hi] = np.where(off, 0, rr)
        idle_run[:, lo:hi] = rnd(idle_run[:, lo:hi] + (o & ~busy))
        x[:, t] = lo + o.sum(axis=1)
        top = lo + int(np.flatnonzero(o.any(axis=0)).max(initial=-1)) + 1
    if final_off:
        downs = rnd(downs + (on & ~(a[-1] > levels)))
    # busy slots of level l: how many slots have a[t] > l
    busy_slots = np.cumsum(np.bincount(a, minlength=n_levels + 1)[::-1])[::-1][1:]
    run = rnd(idle_run + busy_slots[:n_levels])
    level_cost = rnd(rnd(rnd(run * P) + rnd(ups * b_on)) + rnd(downs * b_off))
    cost = level_cost.sum(axis=1) if acc_dtype != "bfloat16" else _bf16_sum(level_cost, rnd)
    return {"x": x, "level_cost": level_cost, "cost": cost}


def _bf16_sum(v, rnd):
    """Row sums accumulated in bfloat16, pairwise as numpy sums."""
    v = rnd(v)
    while v.shape[1] > 1:
        if v.shape[1] % 2:
            v = np.concatenate([v, np.zeros((v.shape[0], 1), v.dtype)], axis=1)
        v = rnd(v[:, 0::2] + v[:, 1::2])
    return v[:, 0]


# ---------------------------------------------------------------------------
# Wait draws as input data: the engine's split and transform, copied
# ---------------------------------------------------------------------------

def uniforms(key, T: int, n_levels: int):
    """Two (T, N) U(0, 1) tables from ``key``: the atom draw (A3) and the
    value draw, split as the engine splits them."""
    import jax

    k0, k1 = jax.random.split(key)
    return (jax.random.uniform(k0, (T, n_levels)),
            jax.random.uniform(k1, (T, n_levels)))


def waits_from_uniforms(u0, u, window, delta, *, atom: bool):
    """A2: span * log1p(u (e - 1)) with span = (1 - alpha) Delta; A3
    (``atom``) adds an atom at 0 of mass alpha / (e - 1 + alpha).  alpha =
    (w + 1) / Delta clipped to [0, 1]; float32, as the engine computes it."""
    import jax.numpy as jnp

    b = jnp.asarray(delta, jnp.float32)
    alpha = jnp.clip((jnp.asarray(window, jnp.float32) + 1.0) / b, 0.0, 1.0)
    span = (1.0 - alpha) * b
    w = span * jnp.log1p(u * (E - 1.0))
    if atom:
        p0 = alpha / (E - 1.0 + alpha)
        w = jnp.where(u0 < p0, 0.0, w)
    return w


def wait_tables(key, T: int, n_levels: int, windows, delta, *, atom: bool):
    """(W, T, N) float32 host table of the waits for each window."""
    import jax

    u0, u = uniforms(key, T, n_levels)
    fn = jax.jit(waits_from_uniforms, static_argnames="atom")
    return np.stack([np.asarray(fn(u0, u, w, delta, atom=atom)) for w in windows])
