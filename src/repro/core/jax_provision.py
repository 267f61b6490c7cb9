"""The paper's provisioning algorithms as a batched, jit-able JAX engine.

The fluid-model level decomposition (DESIGN.md §2) makes every algorithm an
independent per-level computation, so the whole fleet is one vectorized
``lax.scan`` over slots.  On top of that single scan this module layers

  * all the policies — ``A1`` (deterministic, ratio ``2 - α``), ``A2``
    (randomized, ``(e-α)/(e-1)``), ``A3`` (randomized, ``e/(e-1+α)``),
    ``offline`` (hindsight optimum, closed form), ``delayedoff``, and the
    typed-fleet pair from the Albers–Quedenfeld line (arXiv 2107.14672):
    ``AQ-det`` (per-type break-even timers, 2d-competitive over d server
    types) and ``AQ-rand`` (randomized per-type waits, d·e/(e−1)) — with
    the randomized waits sampled per level via an explicit PRNG key,
    matching :mod:`repro.core.ski_rental` semantics;
  * heterogeneous per-level cost models: ``Δ``, ``P`` and the toggle costs
    may all be ``(n_levels,)`` arrays (one server type per level), with the
    per-level critical interval driving waits, peek horizons and costs;
    typed fleets (``CostModel.from_groups``) ride the same arrays, with the
    group metadata driving routed level ids and the group-aligned kernel
    block layout in the sharded path;
  * a leading batch axis over demand traces (``(B, T)`` demand, one subkey
    per trace) via ``vmap``;
  * a vectorized sweep axis over prediction windows (``α = (w+1)/Δ``) via
    ``vmap`` with common random numbers across the sweep, so a whole
    (traces × α × policies) competitive-ratio table is one device program;
  * a fused Pallas grid scan (:mod:`repro.kernels.provision_scan`,
    interpret-mode fallback off-TPU) used by the ``shard_map`` fleet path:
    the full (noise-std x window x trace) sweep runs as one kernel program
    per grid cell and level block, with separate scalar-prefetched demand
    and prediction traces indexed per cell — bit-exact against the
    ``lax.scan`` programs above (common random numbers on every axis).

The public entrypoints are :func:`repro.core.provision.provision` and
:func:`repro.core.provision.provision_stream`, driven by a declarative
:class:`~repro.core.provision.ProvisionSpec`.  Each route keeps its own
jitted body (``_run``/``_run_noise_sweep`` and ``_run_stream``/
``_run_stream_noise`` on the lax.scan route, ``_sharded_grid`` and
``_sharded_stream_grid`` on the ``mesh=`` route); each planning decision
they share is made in one helper:

  * which policies draw waits, at which shape and which α —
    :func:`_wait_draws` and :func:`_draw_waits` (and :func:`_n_wait_tables`
    for how many tables the mesh route holds);
  * the (window × trace) sweep of the lax.scan bodies, window-free
    policies broadcast — :func:`_scan_sweep`;
  * the mesh route's storage layout, padded per-level rows, thresholds,
    horizon rows and (S, W, B) cell maps — :func:`_grid_inputs`; its
    ``shard_map`` specs and compaction back to level order —
    :func:`_grid_map`;
  * the mesh route's host prelude (policy and key checks, the static peek
    unroll) — :func:`_sharded_run`.

Semantics mirror :func:`repro.core.fluid.fluid_scan` exactly (tested).

PRNG contract: ``A2``/``A3`` require ``key``.  The engine draws two
``(T, n_levels)`` uniform tables per trace; the draw at ``[t, l]`` is
consumed iff level ``l`` becomes newly idle in slot ``t`` — a pattern that
depends only on the trace (a level enters idle exactly when it stops being
busy), so schedules are reproducible given (trace, key) and independent
draws are never reused across idle periods.  Batched calls split the key
per trace; the α-sweep reuses the same tables across windows (common
random numbers, variance reduction for ratio curves).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs import provenance as _prov

E = math.e

POLICIES = ("A1", "A2", "A3", "offline", "delayedoff", "AQ-det", "AQ-rand")
RANDOMIZED = ("A2", "A3")
#: policies that consume a PRNG key (RANDOMIZED plus the typed AQ-rand)
KEYED = RANDOMIZED + ("AQ-rand",)
#: policies with no prediction peek (ski-rental timers only)
NO_PEEK = ("delayedoff", "AQ-det", "AQ-rand")
#: policies whose schedule ignores the window sweep entirely
WINDOW_FREE = ("offline",) + NO_PEEK


def _check_policy(policy: str) -> None:
    if policy not in POLICIES:
        raise ValueError(
            f"unknown policy {policy!r}: valid policies are {POLICIES}"
        )


def _require_key(policy: str, key) -> None:
    if key is None:
        raise ValueError(f"policy {policy!r} is randomized: pass an explicit key")


# ---------------------------------------------------------------------------
# Randomized-wait sampling (ski-rental thresholds)
# ---------------------------------------------------------------------------

def _uniforms(key: jax.Array, T: int, n_levels: int) -> tuple[jax.Array, jax.Array]:
    """Two (T, n_levels) U(0,1) tables: atom draw (A3) and value draw."""
    k0, k1 = jax.random.split(key)
    return (
        jax.random.uniform(k0, (T, n_levels)),
        jax.random.uniform(k1, (T, n_levels)),
    )


def _waits_from_uniforms(policy, u0, u, window, delta):
    """Transform uniform tables into wait thresholds for a given window.

    A2: Z ~ e^{z/((1-α)Δ)} / ((e-1)(1-α)Δ) on [0, (1-α)Δ]  (inverse CDF).
    A3: atom at 0 w.p. α/(e-1+α), else A2's density (corrected atom, see
    ski_rental.py).  AQ-rand: the no-peek α = 0 case — the full-span
    e/(e−1) ski-rental distribution per level, which on a typed fleet is
    the Albers–Quedenfeld randomized per-type wait (d·e/(e−1) overall).
    ``delta`` is a scalar or a per-level ``(N,)`` array — heterogeneous
    fleets get a distinct α and span per level.  Keeping the transform
    separate from the draws lets the α-sweep share draws across windows.
    """
    b = jnp.asarray(delta, jnp.float32)
    alpha = jnp.clip((jnp.asarray(window, jnp.float32) + 1.0) / b, 0.0, 1.0)
    if policy == "AQ-rand":             # no peek: the window never enters
        alpha = jnp.zeros_like(alpha)
    span = (1.0 - alpha) * b
    waits = span * jnp.log1p(u * (E - 1.0))
    if policy == "A3":
        p0 = alpha / (E - 1.0 + alpha)
        waits = jnp.where(u0 < p0, 0.0, waits)
    return waits


def _wait_draws(policy, keys, T, n_levels):
    """Per-trace uniform tables ``(u0, u)``, each (B, T, n_levels), for the
    policies that draw their waits (:data:`KEYED`); None for the rest.

    Drawn per trace at ``n_levels`` — never at the sharded layout's width —
    so the (trace, key) -> schedule contract holds regardless of mesh size
    or group padding; every window reuses them (common random numbers).
    """
    if policy not in KEYED:
        return None
    return jax.vmap(lambda k: _uniforms(k, T, n_levels))(keys)


def _draw_waits(policy, draws, window, delta):
    """One trace's (T, N) wait thresholds from its slice of
    :func:`_wait_draws` (None passes through).  The transform sees the
    sweep's ``window`` for A2/A3 and window 0 for the window-free AQ-rand,
    whose α it pins to 0."""
    if draws is None:
        return None
    u0, u = draws
    return _waits_from_uniforms(
        policy, u0, u, window if policy in RANDOMIZED else 0, delta)


def _n_wait_tables(policy, n_windows, n_traces):
    """How many (T, N) threshold tables the sharded bodies hold for a
    (W, B) sweep: one per window and trace for A2/A3, one per trace for
    the window-free AQ-rand, none where the thresholds are constant rows."""
    if policy not in KEYED:
        return 0
    return n_traces * (1 if policy in WINDOW_FREE else n_windows)


# ---------------------------------------------------------------------------
# The per-level slot scan (all online policies)
# ---------------------------------------------------------------------------

def _slot_update(r, on, wait, busy, seen, wait_draw):
    """One slot of the per-level ski-rental engine (shared by the monolithic
    and the chunked scan bodies — byte-identical op order, so the streaming
    path is bit-exact against :func:`_on_matrix_scan` by construction).

    ``r``/``on``/``wait``: (N,) idle run length, on bit, wait threshold;
    ``busy``: dispatcher compare for this slot; ``seen``: peek verdict;
    ``wait_draw``: this slot's sampled thresholds (None for deterministic
    policies, whose ``wait`` is the static threshold).  Returns the updated
    state plus the ``expired``/``off_now`` decision bits (provenance).
    """
    on = on | busy                                 # dispatcher turn-on
    r = jnp.where(busy, 0.0, r)
    idle = on & ~busy
    if wait_draw is not None:
        wait = jnp.where(idle & (r == 0.0), wait_draw, wait)
    r = jnp.where(idle, r + 1.0, r)
    expired = idle & (r - 1.0 >= wait)
    off_now = expired & ~seen
    on = on & ~off_now
    r = jnp.where(off_now, 0.0, r)
    return (r, on, wait), expired, off_now


def _on_matrix_scan(a, pred, levels, *, delta, max_h, window, policy, waits=None,
                    record=False):
    """(T, N) bool on-matrix via one lax.scan over slots.

    ``delta`` is a scalar or per-level ``(N,)`` array of critical intervals;
    ``max_h`` is the static peek bound (``ceil(max Δ)`` — the peek never
    exceeds the largest critical interval).  ``window`` may be a python int
    or a traced scalar (the α-sweep vmaps over it).  ``waits``: (T, N)
    sampled thresholds for A2/A3; the entry at ``[t, l]`` is consumed iff
    level ``l`` becomes newly idle in slot ``t``.

    ``record=True`` (a python-time switch: the default trace is unchanged)
    additionally emits per-slot decision provenance and returns
    ``(ons, codes)`` with ``codes`` (T, N) uint8 — the
    :mod:`repro.obs.provenance` reason bitmask (demand-rise / wait-expired /
    peek-fired / toggle-off) for every (slot, level).
    """
    T = a.shape[0]
    n = levels.shape[0]
    b = jnp.broadcast_to(jnp.asarray(delta, jnp.float32), (n,))
    pad = jnp.concatenate([pred, jnp.zeros((max_h,), pred.dtype)])
    w = jnp.asarray(window, jnp.float32)
    if policy in NO_PEEK:           # timer Δ_l (the per-type break-even
        horizon = jnp.zeros((n,), jnp.float32)   # timer for AQ-det), no peek
        m_static = b
    else:
        horizon = jnp.minimum(w + 1.0, b)
        m_static = jnp.maximum(0.0, b - w - 1.0)
    hslots = jnp.arange(max_h, dtype=jnp.float32)

    def step(carry, t):
        r, on, wait = carry                            # (N,) f32, bool, f32
        busy = a[t] > levels
        if record:
            rise = busy & ~on                          # dispatcher turn-on edge
        fut = jax.lax.dynamic_slice(pad, (t + 1,), (max_h,))
        seen = (
            (fut[None, :] > levels[:, None]) & (hslots[None, :] < horizon[:, None])
        ).any(axis=1)
        (r, on, wait), expired, off_now = _slot_update(
            r, on, wait, busy, seen, None if waits is None else waits[t]
        )
        if record:
            codes = (
                rise.astype(jnp.uint8) * _prov.DEMAND_RISE
                + expired.astype(jnp.uint8) * _prov.WAIT_EXPIRED
                + (expired & seen).astype(jnp.uint8) * _prov.PEEK_FIRED
                + off_now.astype(jnp.uint8) * _prov.TOGGLE_OFF
            )
            return (r, on, wait), (on, codes)
        return (r, on, wait), on

    init = (
        jnp.zeros((n,), jnp.float32),
        a[0] > levels,                                  # x(0) = a(0)
        m_static if waits is None else jnp.zeros((n,), jnp.float32),
    )
    (_, _, _), out = jax.lax.scan(step, init, jnp.arange(T))
    return out


def _stream_cell(a, pred, levels, *, delta, max_h, window, policy, waits=None,
                 t_chunk, record=False, lane_ok=None):
    """Chunked slot scan over one (trace, window) cell with explicit carry.

    The streaming twin of :func:`_on_matrix_scan`: instead of materializing
    the (T, N) on-matrix, slots run in ``t_chunk`` tiles under an outer
    ``lax.scan`` whose carry is the O(N) engine state (idle run, on bits,
    wait thresholds) plus int32 accumulators — so only x(t) and per-level
    totals ever leave the scan and the working set is O(t_chunk · N)
    regardless of T.  The slot body is the shared :func:`_slot_update`, so
    the state trajectory is bit-identical to the monolithic scan.

    Toggle accounting uses the virtual-boundary convention: the "previous"
    state at t = 0 is the busy mask itself, which makes ``up`` absorb
    ``_cost_terms``' ``first_on`` and makes the t = 0 ``down`` vanish; the
    forced x(T) = a(T) final off is added here from the end-of-trace carry.
    The resulting integer totals equal :func:`_cost_terms` of the monolithic
    on-matrix exactly.

    Returns ``(x, terms, on_final)``: ``x`` (T,) int32, ``terms`` a dict of
    (N,) int32 totals ``run``/``up``/``down`` (plus the four
    :data:`repro.obs.provenance.COUNT_ORDER` counters when ``record``), and
    ``on_final`` the (N,) end-of-trace on bits (the sharded path recomputes
    its own routed final-off from these).  ``lane_ok``: optional (N,) bool
    storage-lane mask (the sharded layout's pad lanes) applied to x and
    every accumulator, mirroring the Pallas kernels' lane masking.
    """
    T = a.shape[0]
    n = levels.shape[0]
    b = jnp.broadcast_to(jnp.asarray(delta, jnp.float32), (n,))
    w = jnp.asarray(window, jnp.float32)
    if policy in NO_PEEK:
        horizon = jnp.zeros((n,), jnp.float32)
        m_static = b
    else:
        horizon = jnp.minimum(w + 1.0, b)
        m_static = jnp.maximum(0.0, b - w - 1.0)
    hslots = jnp.arange(max_h, dtype=jnp.float32)
    ok = jnp.ones((n,), bool) if lane_ok is None else lane_ok

    n_chunks = -(-T // t_chunk)
    T_pad = n_chunks * t_chunk
    a_pad = jnp.concatenate([a, jnp.zeros((T_pad - T,), a.dtype)])
    p_pad = jnp.concatenate([pred, jnp.zeros((T_pad - T + max_h,), pred.dtype)])
    w_pad = (
        None if waits is None
        else jnp.concatenate([waits, jnp.zeros((T_pad - T, n), waits.dtype)])
    )
    n_acc = 7 if record else 3
    init = (
        (
            jnp.zeros((n,), jnp.float32),                       # idle run r
            jnp.zeros((n,), bool),          # on (slot 0's |busy seeds x(0)=a(0))
            m_static if waits is None else jnp.zeros((n,), jnp.float32),
        ),
        jnp.zeros((n_acc, n), jnp.int32),
    )

    def chunk(carry, c):
        state, accs = carry
        t0 = c * t_chunk
        a_c = jax.lax.dynamic_slice(a_pad, (t0,), (t_chunk,))
        p_c = jax.lax.dynamic_slice(p_pad, (t0,), (t_chunk + max_h,))
        w_c = (
            None if w_pad is None
            else jax.lax.dynamic_slice(w_pad, (t0, 0), (t_chunk, n))
        )

        def slot(carry2, tl):
            (r, on, wait), accs = carry2
            t = t0 + tl
            valid = t < T                       # pad tail freezes everything
            busy = a_c[tl] > levels
            prev_eff = jnp.where(t == 0, busy, on)    # virtual x(0)=a(0) edge
            rise = busy & ~prev_eff
            fut = jax.lax.dynamic_slice(p_c, (tl + 1,), (max_h,))
            seen = (
                (fut[None, :] > levels[:, None])
                & (hslots[None, :] < horizon[:, None])
            ).any(axis=1)
            (r2, on2, wait2), expired, off_now = _slot_update(
                r, on, wait, busy, seen, None if w_c is None else w_c[tl]
            )
            x_t = jnp.where(valid, (on2 & ok).sum().astype(jnp.int32), 0)
            rows = [on2 & ok, (on2 & ~prev_eff) & ok, (prev_eff & ~on2) & ok]
            if record:
                rows += [
                    rise & ok, expired & ok, (expired & seen) & ok, off_now & ok,
                ]
            inc = jnp.stack([x.astype(jnp.int32) for x in rows])
            accs = jnp.where(valid, accs + inc, accs)
            r2 = jnp.where(valid, r2, r)
            on2 = jnp.where(valid, on2, on)
            wait2 = jnp.where(valid, wait2, wait)
            return ((r2, on2, wait2), accs), x_t

        (state, accs), x_c = jax.lax.scan(slot, (state, accs),
                                          jnp.arange(t_chunk))
        return (state, accs), x_c

    ((_, on_f, _), accs), xs = jax.lax.scan(chunk, init, jnp.arange(n_chunks))
    x = xs.reshape(T_pad)[:T]
    final_off = ((on_f & ok) & ~(a[T - 1] > levels)).astype(jnp.int32)
    terms = {"run": accs[0], "up": accs[1], "down": accs[2] + final_off}
    if record:
        for k, name in enumerate(_prov.COUNT_ORDER):
            terms[name] = accs[3 + k]
    return x, terms, on_f


def _offline_levels(a, n_levels, delta):
    """Hindsight-optimal per-level schedule, closed form (no scan).

    Level on at slot t iff busy, or inside an interior idle gap of length
    <= Delta_l (prev and next busy exist and next - prev - 1 <= b_l); the
    per-level Delta makes this heterogeneous-ready.
    """
    T = a.shape[0]
    b = jnp.broadcast_to(jnp.asarray(delta, jnp.float32), (n_levels,))
    levels = jnp.arange(n_levels)
    busy = a[:, None] > levels[None, :]                    # (T, N)
    idx = jnp.arange(T)[:, None]
    prev_busy = jax.lax.associative_scan(
        jnp.maximum, jnp.where(busy, idx, -1), axis=0
    )                                                      # last busy <= t
    next_busy = jax.lax.associative_scan(
        jnp.minimum, jnp.where(busy, idx, T + b + 1), axis=0, reverse=True
    )                                                      # first busy >= t
    gap = next_busy - prev_busy - 1
    keep_idle = (prev_busy >= 0) & (next_busy <= T - 1) & (gap * 1.0 <= b)
    return busy | (~busy & keep_idle)


def _level_schedule(a, n_levels, delta, window, policy, predicted=None, key=None):
    """(T, n_levels) bool on-matrix for one trace (any policy).

    ``delta`` must be concrete (a python number or per-level array) — this
    convenience wrapper derives the static peek bound from it.
    """
    _check_policy(policy)
    max_h = int(math.ceil(float(jnp.max(jnp.asarray(delta)))))
    pred = a if predicted is None else predicted
    if policy == "offline":
        return _offline_levels(a, n_levels, delta)
    waits = None
    if policy in KEYED:
        _require_key(policy, key)
        waits = _draw_waits(policy, _uniforms(key, a.shape[0], n_levels),
                            window, delta)
    levels = jnp.arange(n_levels)
    return _on_matrix_scan(
        a, pred, levels, delta=delta, max_h=max_h, window=window, policy=policy,
        waits=waits,
    )


# ---------------------------------------------------------------------------
# Per-level cost reduction (heterogeneous-ready)
# ---------------------------------------------------------------------------

def _cost_terms(a, on_matrix, P_lv, beta_on_lv, beta_off_lv, levels=None):
    """Per-level cost components of a schedule, each ``(..., N)``.

    ``a`` (..., T) demand, ``on_matrix`` (..., T, N); the cost fields are
    scalars or ``(N,)`` arrays.  ``levels``: the level ids the on-matrix
    columns correspond to (defaults to 0..N-1; the sharded path passes its
    block's offset ids).  Initial state x(0)=a(0) is free; the final slot is
    forced to x(T)=a(T) (paper eq. 5).
    """
    ob = on_matrix.astype(bool)
    on = ob.astype(jnp.int32)
    if levels is None:
        levels = jnp.arange(on_matrix.shape[-1])
    run_slots = on.sum(axis=-2)                                   # (..., N)
    up = jnp.clip(on[..., 1:, :] - on[..., :-1, :], 0).sum(axis=-2)
    down = jnp.clip(on[..., :-1, :] - on[..., 1:, :], 0).sum(axis=-2)
    first_on = (ob[..., 0, :] & ~(a[..., 0, None] > levels)).astype(jnp.int32)
    final_off = (ob[..., -1, :] & ~(a[..., -1, None] > levels)).astype(jnp.int32)
    return {
        "energy": P_lv * run_slots,
        "on_cost": beta_on_lv * (up + first_on),
        "off_cost": beta_off_lv * (down + final_off),
    }


def on_matrix_cost(a, on_matrix, costs):
    """Total cost of a per-level schedule under a (possibly per-level) model.

    ``costs`` is a :class:`repro.core.costs.CostModel`; supports leading
    batch axes: ``a`` (..., T), ``on_matrix`` (..., T, N).
    """
    P_lv, bon_lv, boff_lv = costs.per_level(on_matrix.shape[-1])
    terms = _cost_terms(jnp.asarray(a), on_matrix, P_lv, bon_lv, boff_lv)
    return (terms["energy"] + terms["on_cost"] + terms["off_cost"]).sum(axis=-1)


# ---------------------------------------------------------------------------
# The lax.scan route: (windows × traces × levels) in a single program
# ---------------------------------------------------------------------------

def _scan_sweep(cell, ab, predb, windows, keys, delta, *, n_levels, policy):
    """The (W, B) sweep of the lax.scan bodies :func:`_run` and
    :func:`_run_stream`: ``cell(ai, pi, w, waits)`` — one (window, trace)
    cell's dict of leaves — vmapped over traces and windows, with each
    trace's waits from :func:`_wait_draws` (common random numbers across
    the sweep).  Returns each leaf (W, B, ...).
    """
    draws = _wait_draws(policy, keys, ab.shape[1], n_levels)

    def over_traces(w):
        return jax.vmap(
            lambda ai, pi, d: cell(ai, pi, w, _draw_waits(policy, d, w, delta))
        )(ab, predb, draws)

    if policy in WINDOW_FREE:
        # window-independent policies: compute once, broadcast over the sweep
        # (AQ-rand draws its per-level waits from the key but never peeks,
        # so one sample serves the whole sweep too)
        out = over_traces(0)
        return jax.tree.map(
            lambda o: jnp.broadcast_to(o[None], (windows.shape[0],) + o.shape), out
        )
    return jax.vmap(over_traces)(windows)                # each leaf (W, B, ...)


@functools.partial(jax.jit, static_argnames=("n_levels", "max_h", "policy",
                                             "record"))
def _run(ab, predb, windows, delta, P_lv, beta_on_lv, beta_off_lv, keys, *,
         n_levels, max_h, policy, record=False):
    """Shared engine body behind :func:`repro.core.provision.provision`.

    ``ab``/``predb``: (B, T) int32; ``windows``: (W,); ``delta``/cost
    fields: (N,) float32; ``keys``: (B,) typed keys or None.  Returns a dict
    of ``x`` (W, B, T) int32 and per-level cost terms (W, B, N) float32.
    The cost model enters as pytree *data*, so re-pricing a fleet reuses
    the compiled program — only (policy, shapes) are compile keys.

    ``record=True`` (static) adds ``decisions`` (W, B, T, N) uint8 — the
    per-slot :mod:`repro.obs.provenance` reason bitmask — to the dict; the
    default trace is byte-for-byte today's program.  ``offline`` has no slot
    scan, hence nothing to record (rejected in ``provision``).
    """
    if record and policy == "offline":
        raise ValueError("record=True: offline has no slot scan to record")
    levels = jnp.arange(n_levels)

    def cell(ai, pi, w, waits):
        if policy == "offline":
            ons, codes = _offline_levels(ai, n_levels, delta), None
        else:
            res = _on_matrix_scan(ai, pi, levels, delta=delta, max_h=max_h,
                                  window=w, policy=policy, waits=waits,
                                  record=record)
            ons, codes = res if record else (res, None)
        out = _cost_terms(ai, ons, P_lv, beta_on_lv, beta_off_lv)
        out["x"] = ons.sum(axis=1).astype(jnp.int32)
        if record:
            out["decisions"] = codes
        return out

    return _scan_sweep(cell, ab, predb, windows, keys, delta,
                       n_levels=n_levels, policy=policy)


@functools.partial(jax.jit, static_argnames=("n_levels", "max_h", "policy",
                                             "record"))
def _run_noise_sweep(ab, predb, windows, delta, P_lv, beta_on_lv, beta_off_lv,
                     keys, *, n_levels, max_h, policy, record=False):
    """:func:`_run` vmapped over a leading (S,) predicted-trace axis — the
    ``PredictionNoise.std_frac`` sweep.  Demand, windows and keys are held
    fixed across the sweep (common random numbers).  A separate jitted
    entrypoint (rather than an inline ``vmap`` in ``provision``) so the
    sweep path's compiles land in a countable cache — the eval harness's
    no-recompile guard watches ``_cache_size`` here and on :func:`_run`."""

    def one(predb_s):
        return _run(
            ab, predb_s, windows, delta, P_lv, beta_on_lv, beta_off_lv, keys,
            n_levels=n_levels, max_h=max_h, policy=policy, record=record,
        )

    return jax.vmap(one)(predb)


@functools.partial(jax.jit, static_argnames=("n_levels", "max_h", "policy",
                                             "t_chunk", "record"))
def _run_stream(ab, predb, windows, delta, P_lv, beta_on_lv, beta_off_lv, keys,
                *, n_levels, max_h, policy, t_chunk, record=False):
    """Streaming twin of :func:`_run`: same (W, B) sweep structure, same CRN
    wait tables, but every cell runs through the chunked
    :func:`_stream_cell` — O(B · t_chunk · N) working set instead of the
    monolithic scan's O(B · T · N) on-matrix, so the scan route accepts
    production-length traces.  Bit-exact against :func:`_run` on x and every
    cost leaf (shared :func:`_slot_update` body, shared wait-draw
    transformation).

    Differences from :func:`_run`, by design: ``offline`` is rejected (it is
    closed-form over the whole trace — use :func:`provision`), and
    ``record=True`` yields ``decision_counts`` (W, B, 4, N) aggregates — the
    fleet-path convention — because per-slot (T, N) codes are exactly the
    O(T · N) buffer the streaming layout exists to avoid.  The randomized
    policies still draw their (T, N) uniform tables up front (the CRN
    contract pins draws to absolute slots); deterministic policies carry
    O(N) only.
    """
    if policy == "offline":
        raise ValueError(
            "offline is closed-form over the full trace; the streaming engine "
            "is online-only — use provision() for offline"
        )
    levels = jnp.arange(n_levels)

    def cell(ai, pi, w, waits):
        x, t_, _ = _stream_cell(
            ai, pi, levels, delta=delta, max_h=max_h, window=w, policy=policy,
            waits=waits, t_chunk=t_chunk, record=record,
        )
        out = {
            "energy": P_lv * t_["run"],
            "on_cost": beta_on_lv * t_["up"],
            "off_cost": beta_off_lv * t_["down"],
            "x": x,
        }
        if record:
            out["decision_counts"] = jnp.stack(
                [t_[name] for name in _prov.COUNT_ORDER]
            )                                                    # (4, N)
        return out

    return _scan_sweep(cell, ab, predb, windows, keys, delta,
                       n_levels=n_levels, policy=policy)


@functools.partial(jax.jit, static_argnames=("n_levels", "max_h", "policy",
                                             "t_chunk", "record"))
def _run_stream_noise(ab, predb, windows, delta, P_lv, beta_on_lv, beta_off_lv,
                      keys, *, n_levels, max_h, policy, t_chunk, record=False):
    """:func:`_run_stream` vmapped over a leading (S,) predicted-trace axis
    (the noise sweep), mirroring :func:`_run_noise_sweep` — a separate
    jitted entrypoint so the streaming sweep path's compiles land in a
    countable cache too."""

    def one(predb_s):
        return _run_stream(
            ab, predb_s, windows, delta, P_lv, beta_on_lv, beta_off_lv, keys,
            n_levels=n_levels, max_h=max_h, policy=policy, t_chunk=t_chunk,
            record=record,
        )

    return jax.vmap(one)(predb)


# ---------------------------------------------------------------------------
# Fleet-scale engine body: shard the level axis over the mesh (Pallas scan)
# ---------------------------------------------------------------------------

def _sharded_run(mesh, axis, ab, predb, windows, delta, P_lv, beta_on_lv,
                 beta_off_lv, *, n_levels, max_h, policy, keys=None,
                 use_pallas=True, group_sizes=None, t_chunk=None, record=False):
    """Level-sharded engine over the full (S, W, B) sweep grid.

    ``ab``: (B, T) demand; ``predb``: (S, B, T) predicted traces (S = 1
    without a noise sweep); ``windows``: (W,) concrete window values;
    ``keys``: (B,) per-trace keys for the randomized policies.  Returns the
    same dict as :func:`_run_noise_sweep` — leaves shaped (S, W, B, ...) —
    with levels sharded over ``axis``: through :func:`_sharded_grid` (the
    fused Pallas grid scan, one program per ((s, w, b) cell, level block))
    when ``t_chunk`` is None, else through :func:`_sharded_stream_grid`
    (the streaming kernel in ``t_chunk``-slot tiles; ``provision_stream``
    clamps ``t_chunk`` to the trace).

    Bit-exact against the lax.scan programs: the wait tables are the same
    per-trace uniform draws transformed per window (common random numbers
    across both sweep axes — noise cells share draws outright).  This host
    prelude checks the policy and key and concretizes the static unroll
    bound; the bodies are separate jitted entrypoints so the fleet path's
    compiles land in a countable cache (watched by the eval harness and the
    benchmark smoke gates alongside ``_run``/``_run_noise_sweep``).
    """
    _check_policy(policy)
    if policy == "offline":
        raise ValueError(
            "sharded path supports online policies (offline has no slot scan); "
            f"valid policies are {tuple(p for p in POLICIES if p != 'offline')}"
        )
    if policy in KEYED and keys is None:
        _require_key(policy, None)
    windows = jnp.asarray(windows, jnp.int32)
    if policy in NO_PEEK:
        h_unroll = 0
    else:
        try:
            w_max = int(windows.max())                       # static peek bound
        except jax.errors.ConcretizationTypeError:
            # provision(mesh=...) traced under an outer jit/vmap: the sweep
            # values aren't concrete, so unroll to the Δ bound — the
            # per-cell horizon rows mask the peek to min(w+1, Δ_l) anyway,
            # a wider unroll only costs a few masked compares
            w_max = max_h
        h_unroll = int(min(w_max + 1, max_h))
    args = (jnp.asarray(ab), jnp.asarray(predb), windows, delta, P_lv,
            beta_on_lv, beta_off_lv, keys)
    kw = dict(mesh=mesh, axis=axis, n_levels=n_levels, max_h=max_h,
              h_unroll=h_unroll, policy=policy, use_pallas=use_pallas,
              group_sizes=group_sizes, record=record)
    if t_chunk is None:
        return _sharded_grid(*args, **kw)
    return _sharded_stream_grid(*args, t_chunk=t_chunk, **kw)


#: routing id for pad lanes in the sharded level layout: compares false
#: against any int32 demand, so a pad lane can never turn on
ROUTE_SENTINEL = 2**30


def _group_layout(n_levels, group_sizes, size):
    """Static (route, sel, n_layout) storage layout for the sharded level axis.

    ``route[j]`` is the *routing id* of storage lane ``j`` — the global
    level the busy compare ``a(t) > route[j]`` dispatches against — or
    ``ROUTE_SENTINEL`` for pad lanes.  ``sel[l]`` is the storage lane of
    real level ``l`` (compacts gathered per-lane outputs back to level
    order).  Ungrouped fleets lay levels out contiguously (identical to the
    pre-typed engine).  Typed fleets pad each group to an 8-sublane
    multiple — capped at the kernel's 128-lane block — so no
    threshold/horizon block straddles two server types: each Pallas block
    is group-pure, which is what lets a block carry one type's Δ/waits.
    The tail is padded to a mesh-size multiple either way.
    """
    if group_sizes is None:
        sizes = padded = [int(n_levels)]
    else:
        sizes = [int(s) for s in group_sizes]
        align = min(128, -(-max(sizes) // 8) * 8)
        padded = [-(-s // align) * align for s in sizes]
    n_layout = -(-sum(padded) // size) * size
    route = np.full(n_layout, ROUTE_SENTINEL, np.int32)
    sel = np.empty(n_levels, np.int64)
    off_route = off_lane = 0
    for s, p in zip(sizes, padded):
        route[off_lane:off_lane + s] = np.arange(off_route, off_route + s)
        sel[off_route:off_route + s] = np.arange(off_lane, off_lane + s)
        off_route += s
        off_lane += p
    return route, sel, n_layout


@functools.lru_cache(maxsize=64)
def _layout_lanes(n_levels, group_sizes, size):
    """(storage lanes, pad lanes) of :func:`_group_layout`'s layout: static
    per (fleet, mesh size), so the host computes it once."""
    n_layout = _group_layout(n_levels, group_sizes, size)[2]
    return n_layout, n_layout - int(n_levels)


def _grid_inputs(ab, predb, windows, delta, P_lv, beta_on_lv, beta_off_lv,
                 keys, *, size, n_levels, policy, group_sizes):
    """The operands both sharded bodies hand :func:`_grid_map`, in the
    storage layout of :func:`_group_layout` over ``size`` shards.

    Returns ``(operands, sel, per_shard)``.  ``operands``, in
    :func:`_grid_map`'s order: the demand rows and the (S·B, T) predicted
    rows; the five (G,) cell maps of cell g = (s, w, b) in row-major order
    (demand row, predicted row, threshold row, horizon row, window value —
    the (S, W, B) axis convention of :func:`_run_noise_sweep`); the (K, T
    or 1, n_layout) thresholds (the waits of :func:`_draw_waits` for the
    keyed policies, the timer Δ_l for delayedoff/AQ-det, A1's static
    threshold per window); the (W, n_layout) peek horizons; then Δ, the
    three cost fields and the routing ids, each one (n_layout,) row.
    """
    S, B, T = predb.shape
    W = windows.shape[0]
    route_np, sel_np, n_layout = _group_layout(n_levels, group_sizes, size)
    per_shard = n_layout // size
    route = jnp.asarray(route_np)
    sel = jnp.asarray(sel_np)

    def pad_lv(v, fill):
        # scatter a real (n_levels,) row into the storage layout; pad lanes
        # take ``fill`` (they are masked out of every output anyway)
        v = jnp.broadcast_to(jnp.asarray(v, jnp.float32), (n_levels,))
        return jnp.full((n_layout,), fill, jnp.float32).at[sel].set(v)

    b_real = jnp.broadcast_to(jnp.asarray(delta, jnp.float32), (n_levels,))
    b = pad_lv(delta, 1.0)          # padded lanes are masked out; Δ irrelevant
    wf = windows.astype(jnp.float32)
    draws = _wait_draws(policy, keys, T, n_levels)
    if draws is not None:
        def tables(w):                                       # (B, T, N)
            return jax.vmap(lambda d: _draw_waits(policy, d, w, b_real))(draws)

        # scatter the tables into the layout: one per (window, trace) for
        # A2/A3, one per trace serving the whole sweep for AQ-rand
        waits = tables(0) if policy in WINDOW_FREE else jax.vmap(tables)(wf)
        thresholds = (
            jnp.zeros(waits.shape[:-1] + (n_layout,), jnp.float32)
            .at[..., sel].set(waits)
            .reshape(_n_wait_tables(policy, W, B), T, n_layout)
        )
    elif policy in NO_PEEK:
        thresholds = jnp.broadcast_to(b, (W, n_layout))[:, None, :]  # timer Δ_l
    else:                                                    # A1 per window
        thresholds = jnp.maximum(0.0, b[None, :] - wf[:, None] - 1.0)[:, None, :]
    if policy in NO_PEEK:
        horizon_wl = jnp.zeros((W, n_layout), jnp.float32)   # no peek
    else:
        horizon_wl = jnp.minimum(wf[:, None] + 1.0, b[None, :])
    P_pad = pad_lv(P_lv, 0.0)
    bon_pad = pad_lv(beta_on_lv, 0.0)
    boff_pad = pad_lv(beta_off_lv, 0.0)

    s_ix, w_ix, b_ix = jnp.meshgrid(
        jnp.arange(S), jnp.arange(W), jnp.arange(B), indexing="ij"
    )
    cell_trace = b_ix.reshape(-1).astype(jnp.int32)
    cell_pred = (s_ix * B + b_ix).reshape(-1).astype(jnp.int32)
    if draws is None:
        cell_thr = w_ix.reshape(-1).astype(jnp.int32)
    elif policy in WINDOW_FREE:
        cell_thr = b_ix.reshape(-1).astype(jnp.int32)        # per-trace tables
    else:
        cell_thr = (w_ix * B + b_ix).reshape(-1).astype(jnp.int32)
    cell_hor = w_ix.reshape(-1).astype(jnp.int32)
    cell_w = windows[w_ix.reshape(-1)]
    pred_rows = predb.reshape(S * B, T)
    operands = (ab, pred_rows, cell_trace, cell_pred, cell_thr, cell_hor,
                cell_w, thresholds, horizon_wl, b, P_pad, bon_pad, boff_pad,
                route)
    return operands, sel, per_shard


def _grid_map(local, operands, sel, *, mesh, axis, record):
    """Run a sharded body's per-shard ``local`` over :func:`_grid_inputs`'
    operands under ``shard_map``: traces, cell maps and the sweep axes
    replicated, the level axis of every row sharded over ``axis``; every
    output leaf replicated, then compacted from the storage layout back to
    level order (a no-op slice for ungrouped fleets, where ``sel`` is
    contiguous)."""
    out_spec = {"x": P(), "energy": P(), "on_cost": P(), "off_cost": P()}
    if record:
        out_spec["decision_counts"] = P()
    cell_spec = (P(),) * 5
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P()) + cell_spec
        + (P(None, None, axis), P(None, axis), P(axis), P(axis), P(axis),
           P(axis), P(axis)),
        out_specs=out_spec,
        check_vma=False,    # no replication rule for pallas_call yet
    )
    out = fn(*operands)
    return {
        k: (v if k == "x" else v[..., sel]) for k, v in out.items()
    }


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis", "n_levels", "max_h", "h_unroll", "policy", "use_pallas",
    "group_sizes", "record"))
def _sharded_grid(ab, predb, windows, delta, P_lv, beta_on_lv, beta_off_lv,
                  keys, *, mesh, axis, n_levels, max_h, h_unroll, policy,
                  use_pallas, group_sizes=None, record=False):
    """One device program for the sharded (S, W, B) grid.

    The demand/predicted traces and the per-cell wait tables are replicated
    only along the sweep axes; the *level* axis — thresholds, peek
    horizons, Δ, cost fields — is sharded over the mesh
    (:func:`_grid_inputs`, :func:`_grid_map`).  Each shard runs every grid
    cell over its level block through the Pallas grid scan (interpret mode
    off-TPU); x(t) is a psum and the per-level cost terms a tiled
    all_gather, so the caller sees (S, W, B, ...) leaves identical to the
    unsharded engine.  Scales to fleets far past one host's memory (1000+
    node deployments decide locally, paper Sec. IV).

    Typed fleets (``group_sizes``): levels are stored in the group-aligned
    layout of :func:`_group_layout` and every lane carries its *routing id*
    explicitly — the kernel's dispatcher compares demand against the routed
    id, not the storage position — so group padding never shifts the demand
    split and gathered outputs compact back to level order via ``sel``.

    ``record=True`` (static) adds ``decision_counts`` (S, W, B, 4, N) int32
    to the dict: aggregate per-level reason counters in
    :data:`repro.obs.provenance.COUNT_ORDER` row order.  The fleet path
    records *aggregates only* — streaming (G, T, N) uint8 codes out of the
    kernel would dwarf the on-matrix itself; docs/observability.md spells
    out the asymmetry with the lax.scan path's full per-slot codes.
    """
    from repro.kernels.provision_scan import provision_scan_grid

    S, B, T = predb.shape
    W = windows.shape[0]
    operands, sel, per_shard = _grid_inputs(
        ab, predb, windows, delta, P_lv, beta_on_lv, beta_off_lv, keys,
        size=mesh.shape[axis], n_levels=n_levels, policy=policy,
        group_sizes=group_sizes)

    def local(a_rows, p_rows, ct, cp, cthr, chor, cw, thr_l, hor_l, b_l,
              Pp, bon, boff, route_l):
        counts = None
        if use_pallas:
            out = provision_scan_grid(
                a_rows, p_rows, thr_l, ct, cp, cthr, chor,
                delta=max_h, horizon=h_unroll, routes=route_l,
                level_horizon=hor_l, record=record,
            )                                          # (G, T, per_shard)
            ons, counts = out if record else (out, None)
        else:
            def per_cell(bi, pi, ti, w):
                waits = thr_l[ti] if policy in KEYED else None
                return _on_matrix_scan(
                    a_rows[bi], p_rows[pi], route_l, delta=b_l, max_h=max_h,
                    window=w, policy=policy, waits=waits, record=record,
                )
            if record:
                ons, codes = jax.vmap(per_cell)(ct, cp, cthr, cw)
                counts = jnp.stack(
                    [((codes & bit) != 0).sum(axis=1) for bit in _prov.COUNT_BITS],
                    axis=1,
                ).astype(jnp.int32)                    # (G, 4, per_shard)
            else:
                ons = jax.vmap(per_cell)(ct, cp, cthr, cw)
        # pad lanes carry ROUTE_SENTINEL and can never turn on; the mask
        # keeps x(t) robust to any lane whose routed id fell off the fleet
        lane_ok = route_l < n_levels
        ons = ons & lane_ok[None, None, :]
        x = jax.lax.psum(ons.sum(axis=-1).astype(jnp.int32), axis)
        ons = ons.reshape(S, W, B, T, per_shard)
        a_swb = jnp.broadcast_to(a_rows[None, None], (S, W, B, T))
        terms = _cost_terms(a_swb, ons, Pp, bon, boff, levels=route_l)
        terms = {
            k: jax.lax.all_gather(v, axis, axis=3, tiled=True)
            for k, v in terms.items()
        }
        terms["x"] = x.reshape(S, W, B, T)
        if record:
            counts = counts * lane_ok[None, None, :].astype(jnp.int32)
            counts = counts.reshape(S, W, B, 4, per_shard)
            terms["decision_counts"] = jax.lax.all_gather(
                counts, axis, axis=4, tiled=True
            )
        return terms

    return _grid_map(local, operands, sel, mesh=mesh, axis=axis, record=record)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis", "n_levels", "max_h", "h_unroll", "policy", "use_pallas",
    "group_sizes", "t_chunk", "record"))
def _sharded_stream_grid(ab, predb, windows, delta, P_lv, beta_on_lv,
                         beta_off_lv, keys, *, mesh, axis, n_levels, max_h,
                         h_unroll, policy, use_pallas, group_sizes=None,
                         t_chunk, record=False):
    """One device program for the streaming sharded grid.

    The level-sharded (S, W, B) grid evaluated through the chunked kernels —
    the HBM-resident double-buffered
    :func:`repro.kernels.provision_scan.provision_scan_stream` on the
    Pallas route, :func:`_stream_cell` on the lax.scan route — so the fleet
    path accepts production-length traces with an O(t_chunk + levels)
    per-cell working set.  The same inputs and ``shard_map`` as
    :func:`_sharded_grid` (:func:`_grid_inputs`, :func:`_grid_map`: same
    CRN draws, same group-aligned routed lanes; bit-exact on x and every
    cost leaf), but each shard reduces its level block to x(t), per-lane
    accumulators and the end-of-trace carry instead of the (G, T,
    per_shard) on-matrix.  The forced x(T) = a(T) final off is applied
    here from the carry (the kernel contract leaves it to the caller, who
    alone knows the trace really ends at T).  Per-slot decision codes are
    never materialized (``record`` yields aggregate counters, the fleet
    path's convention).
    """
    from repro.kernels.provision_scan import provision_scan_stream

    S, B, T = predb.shape
    W = windows.shape[0]
    operands, sel, per_shard = _grid_inputs(
        ab, predb, windows, delta, P_lv, beta_on_lv, beta_off_lv, keys,
        size=mesh.shape[axis], n_levels=n_levels, policy=policy,
        group_sizes=group_sizes)

    def local(a_rows, p_rows, ct, cp, cthr, chor, cw, thr_l, hor_l, b_l,
              Pp, bon, boff, route_l):
        lane_ok = route_l < n_levels
        if use_pallas:
            x_g, accs, carry = provision_scan_stream(
                a_rows, p_rows, thr_l, ct, cp, cthr, chor,
                horizon=h_unroll, t_chunk=t_chunk, n_levels=n_levels,
                routes=route_l, level_horizon=hor_l, record=record,
            )                            # x (G, T); accs/carry lanes (G, per_shard)
            # forced final off: the kernel's down stops at the virtual
            # boundary; close the trace against the routed busy compare
            a_last = a_rows[ct, T - 1]                               # (G,)
            final_off = (
                carry["on"] & lane_ok[None, :]
                & ~(a_last[:, None] > route_l[None, :])
            ).astype(jnp.int32)
            accs = dict(accs)
            accs["down"] = accs["down"] + final_off
        else:
            def per_cell(bi, pi, ti, w):
                waits = thr_l[ti] if policy in KEYED else None
                x, t_, _ = _stream_cell(
                    a_rows[bi], p_rows[pi], route_l, delta=b_l, max_h=max_h,
                    window=w, policy=policy, waits=waits, t_chunk=t_chunk,
                    record=record, lane_ok=lane_ok,
                )
                return x, t_
            x_g, accs = jax.vmap(per_cell)(ct, cp, cthr, cw)
        x = jax.lax.psum(x_g, axis)                              # (G, T)
        terms = {
            "energy": Pp * accs["run"],
            "on_cost": bon * accs["up"],
            "off_cost": boff * accs["down"],
        }
        terms = {
            k: jax.lax.all_gather(
                v.reshape(S, W, B, per_shard), axis, axis=3, tiled=True
            )
            for k, v in terms.items()
        }
        terms["x"] = x.reshape(S, W, B, T)
        if record:
            counts = jnp.stack(
                [accs[name] for name in _prov.COUNT_ORDER], axis=1
            )                                                # (G, 4, per_shard)
            terms["decision_counts"] = jax.lax.all_gather(
                counts.reshape(S, W, B, 4, per_shard), axis, axis=4, tiled=True
            )
        return terms

    return _grid_map(local, operands, sel, mesh=mesh, axis=axis, record=record)
