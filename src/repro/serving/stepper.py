"""The O(1)-state incremental stepper behind ``FleetProvisioner.advance()``.

The monolithic planner re-ran a trailing ``chunk + 3Δ + slack`` window of
history on every call — O(history) work per step and a fresh jit trace per
chunk shape.  This module replaces that with a *true* stepper: the
per-level ski-rental engine state (idle-run clocks, on bits, residual wait
thresholds), the causal deferral window and the queue's age buckets are
carried across calls as an explicit :class:`StepperState`, so each
``advance(chunk)`` costs O(chunk · levels) regardless of how long the
fleet has been running — the memoryless structure the paper's algorithms
have by construction (and what makes them practical at data-center scale,
arXiv 2108.09489 / 2107.14672).

Semantics — *commit-as-returned*:

* every slot's decision is final the moment ``advance`` returns it;
  nothing is replanned when more demand arrives.  The no-peek policies
  (``delayedoff``/``AQ-det``/``AQ-rand``) are therefore **chunk-size
  invariant** — any split of the demand stream yields the identical
  schedule.  Peeking policies read the prediction window *within* the
  chunk only (the future past the chunk boundary has not been observed
  yet, so the peek sees quiet) — at ``T_chunk = 1`` they degrade to their
  no-peek behaviour, which is the honest online semantics of a window the
  operator cannot actually see.
* randomized policies draw each level's wait from
  ``fold_in(key, global_slot)`` at the slot the level goes idle — a
  *slot-indexed* stream, so schedules are chunk-size invariant and
  reproducible from ``(key, demand stream)`` alone.  This is deliberately
  a different stream than the batch planner's per-trace uniform tables
  (those need ``T`` up front, which a stepper never has).
* deferral uses the **causal** :func:`repro.deferral.defer_stream` rule,
  not the batch path's anticipative OA water-filling (docs/deferral.md);
  queue metrics accumulate across calls through
  :func:`repro.deferral.queue_stream`.

Zero steady-state recompiles: chunks are padded to power-of-two buckets
(:func:`pow2_bucket`, tail masked by an ``n_valid`` operand that is jit
*data*), so any mix of chunk sizes within a warmed bucket reuses the
compiled step — gated by a compile-count test in tests/test_streaming.py.
A tick is one compiled program, :func:`stepper_tick`: the scan and the
chunk's per-level and fleet costs together.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jax_provision import (
    KEYED,
    NO_PEEK,
    _slot_update,
    _waits_from_uniforms,
)
from repro.deferral import defer_stream_init, queue_stream_init

#: smallest chunk bucket — sub-8-slot chunks share one compiled step
MIN_BUCKET = 8


def pow2_bucket(n: int) -> int:
    """Smallest power-of-two ≥ ``n`` (floored at :data:`MIN_BUCKET`): the
    padded slot count one compiled step serves.  Steady-state serving with
    any chunk-size mix inside a bucket costs zero recompiles."""
    return max(MIN_BUCKET, 1 << (int(n) - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class StepperState:
    """Everything ``advance()`` carries between calls — O(levels + slack).

    ``t``: global slot counter (how many slots have been committed).
    ``r``/``on``/``wait``: the per-level engine carry — idle-run clocks,
    on bits, residual wait thresholds — exactly the state the streaming
    kernel chains on.  ``defer``/``queue``: the causal-deferral and
    age-bucket queue carries (None when the planner has no deferral spec).
    """

    t: int
    r: jax.Array
    on: jax.Array
    wait: jax.Array
    defer: dict | None = None
    queue: dict | None = None


jax.tree_util.register_dataclass(
    StepperState,
    data_fields=["t", "r", "on", "wait", "defer", "queue"],
    meta_fields=[],
)


def stepper_init(n_levels: int, delta_lv, *, policy: str, window: int = 0,
                 deferral=None) -> StepperState:
    """Fresh carry: clocks at zero, everything off, deterministic waits
    pre-loaded with the static threshold — the full break-even timer Δ_l
    for the no-peek policies, ``max(0, Δ_l − w − 1)`` for the peeking A1
    (the batch engine's ``m_static``); randomized policies start at zero
    and draw theirs at first idle from the slot-indexed stream."""
    b = jnp.broadcast_to(jnp.asarray(delta_lv, jnp.float32), (n_levels,))
    if policy in KEYED:
        wait0 = jnp.zeros((n_levels,), jnp.float32)
    elif policy in NO_PEEK:
        wait0 = b
    else:
        wait0 = jnp.maximum(0.0, b - jnp.float32(window) - 1.0)
    return StepperState(
        t=0,
        r=jnp.zeros((n_levels,), jnp.float32),
        on=jnp.zeros((n_levels,), bool),
        wait=wait0,
        defer=None if deferral is None else defer_stream_init(deferral.bound()),
        queue=None if deferral is None else queue_stream_init(deferral.bound()),
    )


@functools.partial(jax.jit, static_argnames=("policy", "n_levels", "max_h",
                                             "window", "t_pad"))
def stepper_chunk(a_pad, n_valid, t0, key, r, on, wait, delta_lv, *,
                  policy, n_levels, max_h, window, t_pad):
    """One committed chunk of the per-level engine, jitted.

    ``a_pad``: (t_pad,) int32 demand, zero-padded past ``n_valid`` (jit
    *data* — the pad mask freezes state, so bucket padding never changes
    results); ``t0``: global slot of ``a_pad[0]``; ``key``: the planner's
    PRNG key (ignored for deterministic policies); ``r``/``on``/``wait``:
    the (N,) engine carry in.  Static keys are (policy, n_levels, max_h,
    window, t_pad) — none change across a serving loop, so the steady
    state replays one compiled program.

    Returns ``(x, (r, on, wait), totals)``: the (t_pad,) replica counts
    (zeros past ``n_valid``), the carry out, and the chunk's per-level
    ``run``/``up``/``down`` int32 totals (toggle edges against the carried
    state; the virtual x(0)=a(0) boundary applies only at ``t0 = 0``).
    The peek reads the chunk itself (the stepper's demand is already the
    best per-slot prediction) and sees quiet past the chunk end.
    """
    levels = jnp.arange(n_levels)
    b = jnp.broadcast_to(jnp.asarray(delta_lv, jnp.float32), (n_levels,))
    wf = jnp.float32(window)
    if policy in NO_PEEK:
        horizon = jnp.zeros((n_levels,), jnp.float32)
    else:
        horizon = jnp.minimum(wf + 1.0, b)
    hslots = jnp.arange(max_h, dtype=jnp.float32)
    a_pad = jnp.asarray(a_pad, jnp.int32)
    p_pad = jnp.concatenate([a_pad, jnp.zeros((max_h,), jnp.int32)])

    if policy in KEYED:
        def draw(tg):
            k0, k1 = jax.random.split(jax.random.fold_in(key, tg))
            return (jax.random.uniform(k0, (n_levels,)),
                    jax.random.uniform(k1, (n_levels,)))

        u0, u = jax.vmap(draw)(t0 + jnp.arange(t_pad))
        waits_tab = _waits_from_uniforms(policy, u0, u, window, b)
    else:
        waits_tab = None

    def slot(carry, tl):
        r, on, wait, run, up, down = carry
        valid = tl < n_valid
        busy = a_pad[tl] > levels
        prev_eff = jnp.where(t0 + tl == 0, busy, on)   # virtual x(0)=a(0)
        fut = jax.lax.dynamic_slice(p_pad, (tl + 1,), (max_h,))
        seen = (
            (fut[None, :] > levels[:, None]) & (hslots[None, :] < horizon[:, None])
        ).any(axis=1)
        (r2, on2, wait2), _, _ = _slot_update(
            r, on, wait, busy, seen,
            None if waits_tab is None else waits_tab[tl],
        )
        x_t = jnp.where(valid, on2.sum().astype(jnp.int32), 0)
        run = jnp.where(valid, run + on2.astype(jnp.int32), run)
        up = jnp.where(valid, up + (on2 & ~prev_eff).astype(jnp.int32), up)
        down = jnp.where(valid, down + (prev_eff & ~on2).astype(jnp.int32), down)
        r2 = jnp.where(valid, r2, r)
        on2 = jnp.where(valid, on2, on)
        wait2 = jnp.where(valid, wait2, wait)
        return (r2, on2, wait2, run, up, down), x_t

    z = jnp.zeros((n_levels,), jnp.int32)
    (r, on, wait, run, up, down), x = jax.lax.scan(
        slot, (r, on, wait, z, z, z), jnp.arange(t_pad)
    )
    return x, (r, on, wait), {"run": run, "up": up, "down": down}


def tick_input(chunk, t0: int, t_pad: int) -> np.ndarray:
    """The host half of :func:`stepper_tick`'s one upload: a ``(t_pad + 2,)``
    int32 array holding ``chunk`` zero-padded to ``t_pad`` slots, then the
    chunk's length, then ``t0``, its global start slot."""
    out = np.zeros(t_pad + 2, np.int32)
    out[:len(chunk)] = chunk
    out[t_pad:] = len(chunk), t0
    return out


@functools.partial(jax.jit, static_argnames=("policy", "max_h", "window"))
def stepper_tick(tick_in, key, r, on, wait, costs, delta, served=None, *,
                 policy, max_h, window):
    """One ``advance()`` tick as one compiled program: the
    :func:`stepper_chunk` scan and the chunk's costs.

    ``tick_in``: :func:`tick_input`'s array on the device, so a tick's
    demand, length and start slot are one transfer (its length sets the
    bucket ``t_pad``); ``served``: with deferral, the ``(t_pad,)`` service
    profile the scan steps on in place of the chunk's arrivals.
    ``costs``: the planner's :class:`~repro.core.CostModel` with its
    fields as float32 arrays — they are jit data, so re-pricing does not
    recompile, while a typed model's ``group_sizes`` is static structure;
    ``delta``: its critical interval Δ, scalar or per-level, as the host
    computed it (the same float32 values ``provision()`` steps on).  The
    other arguments are :func:`stepper_chunk`'s; its ``n_levels`` is the
    carry's length.

    Returns ``(x, (r, on, wait), fields)``: ``fields`` holds the chunk's
    ``level_cost`` (N,) float32, its sums ``cost``/``energy``/
    ``toggle_cost``, and ``group_cost`` (d,) — None for an ungrouped
    model.  Each per-level product is formed once and feeds both the
    per-level and the fleet totals.
    """
    t_pad = tick_in.shape[0] - 2
    a_pad = tick_in[:t_pad] if served is None else served
    n_levels = r.shape[0]
    x, carry, totals = stepper_chunk(
        a_pad, tick_in[t_pad], tick_in[t_pad + 1], key, r, on, wait, delta,
        policy=policy, n_levels=n_levels, max_h=max_h, window=window,
        t_pad=t_pad,
    )
    P_lv, bon_lv, boff_lv = costs.per_level(n_levels)
    energy = P_lv * totals["run"]
    up = bon_lv * totals["up"]
    down = boff_lv * totals["down"]
    level_cost = energy + up + down
    return x, carry, {
        "cost": level_cost.sum(),
        "energy": energy.sum(),
        "toggle_cost": (up + down).sum(),
        "level_cost": level_cost,
        "group_cost": (None if costs.group_sizes is None
                       else costs.group_reduce(level_cost)),
    }
