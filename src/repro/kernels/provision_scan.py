"""Fused per-level provisioning scan as a Pallas TPU kernel.

The provisioning engine's inner loop (repro.core.jax_provision) is a
sequential scan over slots with an embarrassingly parallel level axis.  For
large fleets the lax.scan path materializes (T, N) intermediates per step;
this kernel fuses the whole scan into one program per (cell, level block):

  grid = (G, N/BN); each program runs ONE sweep cell — a (noise-std,
  window, trace) combination — over its level block, keeping the block's
  state (idle run length, on/off bit, sampled wait threshold) in
  registers/VMEM across all T slots and streaming the on-matrix out row by
  row.  ``G = S*W*B`` covers the full prediction-noise x window x trace
  grid of a :class:`~repro.core.provision.ProvisionSpec` in one launch.

The demand batch ``(B, T)`` and the predicted-trace rows ``(R, T)`` are
scalar-prefetched into SMEM once and *indexed per cell*: four small
``(G,)`` cell maps (also scalar-prefetched) tell each program which demand
row drives its dispatcher compare, which predicted row its peek reads, and
which threshold/horizon table rows it consumes.  The threshold and horizon
tables are blocked into VMEM via scalar-prefetch-driven index maps, so a
program only ever sees its own cell's rows — no HBM traffic beyond those
blocks and the output.

Each lane additionally carries its *routing id* in a blocked ``(1, BN)``
``routes`` row: the dispatcher compares demand against the routed id, not
the lane's storage position.  For a plain fleet the ids are just
``base_level + arange(N)`` (the default), but typed fleets
(``CostModel.from_groups``) store their levels group-aligned — each server
type padded out to its own block boundary so a threshold/horizon block
never straddles two types — and then storage position ≠ level id; the
routes row is what keeps the greedy demand split exact under that packing.
Pad lanes get a sentinel id larger than any demand, so they can never turn
on.

Thresholds are constant rows for the deterministic policies (A1's
``max(0, Δ_l−w−1)`` per window, DELAYEDOFF's and AQ-DET's ``Δ_l``) or
``(T, N)`` tables of sampled waits for A2/A3/AQ-RAND (entry [t, l] is
consumed iff level l becomes newly idle in slot t, matching the engine's
PRNG contract; the table for cell (s, w, b) depends on (w, b) only — noise
sweeps share wait draws — and for the window-free AQ-RAND on b alone).
Heterogeneous fleets give each level its own Δ, hence its own threshold
*and* its own peek reach: ``level_horizon`` rows are per-level floats
masking the statically unrolled ``horizon`` peek to ``min(w+1, Δ_l)``
slots (fractional Δ_l included: slot ``h`` is peeked iff ``h < Δ_l``).

Off-TPU the kernel runs in interpret mode (auto-detected; override with
the ``REPRO_PALLAS_INTERPRET`` env var — see :func:`_resolve_interpret`),
so the sharded fleet path is testable on CPU.

Two kernels share the slot semantics:

  * :func:`provision_scan_grid` — the monolithic layout: whole traces
    scalar-prefetched into SMEM, the on-matrix written as a ``(G, T, BN)``
    VMEM block.  Memory is O(B·T) in SMEM, which caps the horizon long
    before HBM does — fine for planning windows, not for month-long traces.
  * :func:`provision_scan_stream` — the streaming layout: demand/predicted
    rows live in HBM (``pl.ANY``) and are pulled in fixed ``t_chunk``
    tiles with double-buffered async copies into SMEM/VMEM scratch; the
    per-level ``(run-length, on-bit, wait)`` state is carried across tiles
    in registers and returned to the caller, so a call's working set is
    O(t_chunk · BN) regardless of T and consecutive calls chain bit-exactly
    via the carry (see docs/provisioning_engine.md "Streaming & long
    traces").
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BN = 128     # level-block width (lane dimension)
LANE = 128           # TPU lane tile: Mosaic slices a row only in whole tiles

#: default streaming tile length (slots per double-buffered DMA)
DEFAULT_T_CHUNK = 512


def _resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the Pallas execution route and record it as a telemetry gauge.

    ``None`` consults the ``REPRO_PALLAS_INTERPRET`` env var (truthy
    ``1/true/yes/on`` forces interpret mode, falsy ``0/false/no/off``
    forces the compiled route even off-TPU — useful for debugging lowering
    errors on CPU), falling back to backend auto-detection (interpret
    everywhere but TPU).  The chosen route lands on the active telemetry
    registry as the ``kernels/pallas_interpret`` gauge (1 = interpret,
    0 = compiled), so BENCH rows are attributable to hardware.
    """
    if interpret is None:
        env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
        if env in ("1", "true", "yes", "on"):
            interpret = True
        elif env in ("0", "false", "no", "off"):
            interpret = False
        elif env:
            raise ValueError(
                f"REPRO_PALLAS_INTERPRET={env!r}: expected one of "
                "1/true/yes/on or 0/false/no/off (or unset for backend "
                "auto-detection)"
            )
        else:
            interpret = jax.default_backend() != "tpu"
    from repro.obs.telemetry import get_telemetry

    tel = get_telemetry()
    if tel.enabled:
        tel.gauge("kernels/pallas_interpret", 1.0 if interpret else 0.0)
    return bool(interpret)

#: routing id given to pad lanes: larger than any int32 demand value, so a
#: padded lane's dispatcher compare is never true and it can never turn on
PAD_ROUTE = 2**30


def _grid_scan_kernel(
    cb_ref, cp_ref, ct_ref, ch_ref,   # scalar prefetch (SMEM): (G,) cell maps
    a_ref,                            # scalar prefetch (SMEM): (B, T+max_h) demand
    p_ref,                            # scalar prefetch (SMEM): (R, T+max_h) predicted
    m_ref,                            # (1, 1 | T, BN) f32 wait thresholds (cell block)
    h_ref,                            # (1, BN) f32 per-level peek horizon (cell block)
    r_ref,                            # (1, BN) int32 routing ids (level block)
    o_ref,                            # (1, T, BN) int32 on-matrix block
    *rest,                            # record=True: (1, 4, BN) int32 counts block
    T: int, bn: int, horizon: int, time_varying: bool, record: bool = False,
):
    g = pl.program_id(0)
    levels = r_ref[pl.ds(0, 1), :]    # routed level ids for this lane block
    b = cb_ref[g]                     # demand row for this cell
    p = cp_ref[g]                     # predicted row for this cell
    h_row = h_ref[pl.ds(0, 1), :]

    def body(t, carry):
        if record:
            r, on_i, wait, c_rise, c_wait, c_peek, c_off = carry
        else:
            r, on_i, wait = carry                   # (1, BN) f32, int32, f32
        on = on_i != 0                  # Mosaic cannot carry a bool vector
        busy = a_ref[b, t] > levels
        if record:
            # dispatcher turn-on edge; t=0 is the free initial state
            # x(0)=a(0) (the carry starts all-off only as an encoding), so
            # it is not a rise — matching the lax.scan route's init
            rise = busy & ~on & (t > 0)
        on = on | busy                              # dispatcher turn-on
        r = jnp.where(busy, 0.0, r)
        idle = on & ~busy
        if time_varying:
            wait = jnp.where(idle & (r == 0.0), m_ref[0, pl.ds(t, 1), :], wait)
        r = jnp.where(idle, r + 1.0, r)
        seen = jnp.zeros_like(busy)
        for h in range(horizon):                    # static unroll, <= max Delta
            seen = seen | ((p_ref[p, t + 1 + h] > levels) & (float(h) < h_row))
        expired = idle & (r - 1.0 >= wait)
        off_now = expired & ~seen
        on = on & ~off_now
        r = jnp.where(off_now, 0.0, r)
        on_i = on.astype(jnp.int32)
        o_ref[0, pl.ds(t, 1), :] = on_i
        if record:
            return (r, on_i, wait,
                    c_rise + rise.astype(jnp.int32),
                    c_wait + expired.astype(jnp.int32),
                    c_peek + (expired & seen).astype(jnp.int32),
                    c_off + off_now.astype(jnp.int32))
        return (r, on_i, wait)

    init = (
        jnp.zeros((1, bn), jnp.float32),
        jnp.zeros((1, bn), jnp.int32),              # x(0) = a(0): busy turns it on
        jnp.zeros((1, bn), jnp.float32) if time_varying else m_ref[0, pl.ds(0, 1), :],
    )
    if record:
        init = init + tuple(jnp.zeros((1, bn), jnp.int32) for _ in range(4))
    final = jax.lax.fori_loop(0, T, body, init)
    if record:
        c_ref = rest[0]
        for i, cnt in enumerate(final[3:]):         # provenance.COUNT_ORDER rows
            c_ref[0, pl.ds(i, 1), :] = cnt


def provision_scan_grid(
    traces: jax.Array,          # (B, T) int32 demand rows
    predicted: jax.Array,       # (R, T) int32 predicted rows the peek reads
    thresholds: jax.Array,      # (K, 1, N) constant or (K, T, N) sampled waits
    cell_trace: jax.Array,      # (G,) int32 demand row per cell
    cell_pred: jax.Array,       # (G,) int32 predicted row per cell
    cell_thr: jax.Array,        # (G,) int32 threshold-table row per cell
    cell_hor: jax.Array,        # (G,) int32 horizon-table row per cell
    *,
    delta: int,                 # static pad/peek bound: ceil(max per-level Delta)
    horizon: int,               # peek slots unrolled: min(max_w+1, delta), 0 = none
    base_level: jax.Array | int = 0,
    routes: jax.Array | None = None,  # (N,) int32 routed level id per lane
    level_horizon: jax.Array | None = None,  # (H, N) per-level peek reach rows
    block_levels: int = DEFAULT_BN,
    interpret: bool | None = None,
    record: bool = False,
) -> jax.Array:
    """(G, T, N) bool on-matrix: one (noise, window, trace) cell per row.

    Cell ``g`` runs the slot scan with demand ``traces[cell_trace[g]]``,
    peek trace ``predicted[cell_pred[g]]``, wait thresholds
    ``thresholds[cell_thr[g]]`` and per-level peek reach
    ``level_horizon[cell_hor[g]]``.  Lane ``j`` dispatches against level id
    ``routes[j]`` — defaulting to the contiguous ``base_level + j`` — so a
    group-aligned typed layout can interleave pad lanes freely; block
    padding always uses the never-on :data:`PAD_ROUTE` sentinel.

    ``record=True`` returns ``(ons, counts)`` with ``counts`` (G, 4, N)
    int32 — aggregate per-lane decision counters accumulated in the scan
    carry, rows in :data:`repro.obs.provenance.COUNT_ORDER` order
    (demand-rise, wait-expired, peek-fired, toggle-off).  Aggregates, not
    per-slot codes: a (G, T, N) uint8 provenance stream would double the
    kernel's HBM traffic, so full codes stay a lax.scan-path feature.
    """
    traces = jnp.asarray(traces, jnp.int32)
    predicted = jnp.asarray(predicted, jnp.int32)
    assert traces.ndim == 2 and predicted.ndim == 2, (traces.shape, predicted.shape)
    T = traces.shape[1]
    max_h = int(delta)
    assert 0 <= horizon <= max_h, (horizon, delta)
    thresholds = jnp.asarray(thresholds, jnp.float32)
    assert thresholds.ndim == 3, thresholds.shape
    time_varying = thresholds.shape[1] != 1
    n = thresholds.shape[-1]
    G = cell_trace.shape[0]
    bn = block_levels
    n_padded = -(-n // bn) * bn
    pad_n = n_padded - n
    m3d = thresholds
    if level_horizon is None:
        h2d = jnp.full((1, n), float(horizon), jnp.float32)
    else:
        h2d = jnp.asarray(level_horizon, jnp.float32)
    if routes is None:
        routes = jnp.asarray(base_level, jnp.int32) + jnp.arange(n, dtype=jnp.int32)
    r2d = jnp.asarray(routes, jnp.int32).reshape(1, n)
    if pad_n:
        m3d = jnp.pad(m3d, ((0, 0), (0, 0), (0, pad_n)))
        h2d = jnp.pad(h2d, ((0, 0), (0, pad_n)))
        r2d = jnp.pad(r2d, ((0, 0), (0, pad_n)), constant_values=PAD_ROUTE)
    h3d = h2d[:, None, :]           # (H, 1, NP): a legal (1, BN) tile per row
    a_pad = jnp.pad(traces, ((0, 0), (0, max_h)))
    p_pad = jnp.pad(predicted, ((0, 0), (0, max_h)))
    cells = tuple(jnp.asarray(c, jnp.int32) for c in
                  (cell_trace, cell_pred, cell_thr, cell_hor))
    interpret = _resolve_interpret(interpret)

    kernel = functools.partial(
        _grid_scan_kernel, T=T, bn=bn, horizon=horizon,
        time_varying=time_varying, record=record,
    )
    out_specs = pl.BlockSpec((1, T, bn), lambda g, j, *p: (g, 0, j))
    out_shape = jax.ShapeDtypeStruct((G, T, n_padded), jnp.int32)
    if record:
        out_specs = [out_specs, pl.BlockSpec((1, 4, bn), lambda g, j, *p: (g, 0, j))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((G, 4, n_padded), jnp.int32)]
    # index maps receive the scalar-prefetch refs: p[2]/p[3] are the
    # cell -> (threshold row, horizon row) maps, so each program's VMEM
    # blocks are exactly its own cell's tables; the routes row is blocked
    # by level block only (shared across cells)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(G, n_padded // bn),
        in_specs=[
            pl.BlockSpec((1, m3d.shape[1], bn), lambda g, j, *p: (p[2][g], 0, j)),
            pl.BlockSpec((None, 1, bn), lambda g, j, *p: (p[3][g], 0, j)),
            pl.BlockSpec((1, bn), lambda g, j, *p: (0, j)),
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="provision_scan_grid",
    )(*cells, a_pad, p_pad, m3d, h3d, r2d)
    if record:
        ons, counts = out
        return ons[:, :, :n].astype(bool), counts[:, :, :n]
    return out[:, :, :n].astype(bool)


def _stream_scan_kernel(
    cb_ref, cp_ref, ct_ref, ch_ref,   # scalar prefetch (SMEM): (G,) cell maps
    fl_ref,                           # scalar prefetch (SMEM): (2,) [fresh, n_levels]
    a_hbm,                            # ANY: (B, 1, T_pad) demand rows
    p_hbm,                            # ANY: (R, 1, T_pad + p_ext) predicted rows
    m_ref,                            # ANY (K, T_pad, NP) waits | (1, 1, BN) VMEM block
    h_ref,                            # (1, BN) f32 per-level peek horizon (cell block)
    r_ref,                            # (1, BN) int32 routing ids (level block)
    si_ref,                           # (1, 2, BN) f32 carry in: rows [r, wait]
    oni_ref,                          # (1, BN) int32 carry in: on bits
    x_hbm,                            # ANY out: (G, NBLK, 1, T_pad) int32 x partials
    acc_ref,                          # (1, n_acc, BN) int32 out: run/up/down [+counts]
    so_ref,                           # (1, 2, BN) f32 carry out: rows [r, wait]
    ono_ref,                          # (1, BN) int32 carry out: on bits
    *scratch,
    T: int, t_chunk: int, n_tiles: int, bn: int, horizon: int, p_ext: int,
    time_varying: bool, record: bool,
):
    if time_varying:
        (a_scr, p_scr, x_scr, on_scr, thr_scr,
         a_sem, p_sem, x_sem, thr_sem) = scratch
    else:
        a_scr, p_scr, x_scr, on_scr, a_sem, p_sem, x_sem = scratch
    g = pl.program_id(0)
    j = pl.program_id(1)
    b = cb_ref[g]
    pr = cp_ref[g]
    kt = ct_ref[g]
    fresh = fl_ref[0] == 1
    nlv = fl_ref[1]
    levels = r_ref[pl.ds(0, 1), :]
    h_row = h_ref[pl.ds(0, 1), :]
    lane_ok = levels < nlv

    # every row a DMA moves sits alone in its (1, len) tile: Mosaic tiles
    # the last two dims of a buffer, so rows, slots and level blocks are
    # selected along untiled leading dims (the rings are (2, 1, len))
    def a_dma(slot, i):
        return pltpu.make_async_copy(
            a_hbm.at[b, :, pl.ds(i * t_chunk, t_chunk)],
            a_scr.at[slot], a_sem.at[slot],
        )

    def p_dma(slot, i):
        return pltpu.make_async_copy(
            p_hbm.at[pr, :, pl.ds(i * t_chunk, t_chunk + p_ext)],
            p_scr.at[slot], p_sem.at[slot],
        )

    def thr_dma(slot, i):
        return pltpu.make_async_copy(
            m_ref.at[kt, pl.ds(i * t_chunk, t_chunk), pl.ds(j * bn, bn)],
            thr_scr.at[slot], thr_sem.at[slot],
        )

    def x_dma(slot, i):
        return pltpu.make_async_copy(
            x_scr.at[slot],
            x_hbm.at[g, j, :, pl.ds(i * t_chunk, t_chunk)],
            x_sem.at[slot],
        )

    def start_in(slot, i):
        a_dma(slot, i).start()
        p_dma(slot, i).start()
        if time_varying:
            thr_dma(slot, i).start()

    start_in(0, 0)

    if time_varying:
        wait0 = si_ref[0, pl.ds(1, 1), :]
    else:
        wait0 = m_ref[0, pl.ds(0, 1), :]     # constant row; carry is redundant
    init = (
        si_ref[0, pl.ds(0, 1), :],           # r
        oni_ref[pl.ds(0, 1), :],             # on bits (int32: no bool carry)
        wait0,
    ) + tuple(jnp.zeros((1, bn), jnp.int32) for _ in range(7 if record else 3))

    def tile_body(i, st):
        slot = jax.lax.rem(i, 2)
        nxt = 1 - slot

        @pl.when(i + 1 < n_tiles)
        def _():
            start_in(nxt, i + 1)

        a_dma(slot, i).wait()
        p_dma(slot, i).wait()
        if time_varying:
            thr_dma(slot, i).wait()

        # the x slot is reused every other tile: its previous DMA-out must
        # have landed before this tile's reduction overwrites the buffer
        @pl.when(i >= 2)
        def _():
            x_dma(slot, i - 2).wait()

        def slot_body(tl, s):
            if record:
                r, on_i, wait, run, up, down, c1, c2, c3, c4 = s
            else:
                r, on_i, wait, run, up, down = s
            on = on_i != 0
            t_glob = i * t_chunk + tl
            valid = t_glob < T                     # frozen tail of the pad
            first = fresh & (t_glob == 0)
            busy = a_scr[slot, 0, tl] > levels
            # virtual boundary: x(0) = a(0) is the free initial state, so
            # at the very first slot of a fresh trace the previous on-state
            # is the busy pattern itself (no toggle, no rise) — matching
            # _cost_terms' first_on convention; a continuation call's
            # previous state is simply the carried on bits
            # (selected as int32: Mosaic has no select between bool vectors)
            prev_eff = jnp.where(first, busy.astype(jnp.int32), on_i) != 0
            if record:
                rise = busy & ~on & ~first
            on_n = on | busy                       # dispatcher turn-on
            r_n = jnp.where(busy, 0.0, r)
            idle = on_n & ~busy
            if time_varying:
                wait_n = jnp.where(
                    idle & (r_n == 0.0), thr_scr[slot, pl.ds(tl, 1), :], wait
                )
            else:
                wait_n = wait
            r_n = jnp.where(idle, r_n + 1.0, r_n)
            seen = jnp.zeros_like(busy)
            for h in range(horizon):               # static unroll, <= max Delta
                seen = seen | ((p_scr[slot, 0, tl + 1 + h] > levels)
                               & (float(h) < h_row))
            expired = idle & (r_n - 1.0 >= wait_n)
            off_now = expired & ~seen
            on_f = on_n & ~off_now
            r_n = jnp.where(off_now, 0.0, r_n)
            ok = on_f & lane_ok
            # one vector store per slot; the lane sum waits for the tile
            on_scr[pl.ds(tl, 1), :] = ok.astype(jnp.float32)

            def acc(tot, inc):
                return jnp.where(valid, tot + inc.astype(jnp.int32), tot)

            out = (
                jnp.where(valid, r_n, r),
                jnp.where(valid, on_f.astype(jnp.int32), on_i),
                jnp.where(valid, wait_n, wait),
                acc(run, ok),
                acc(up, on_f & ~prev_eff & lane_ok),
                acc(down, prev_eff & ~on_f & lane_ok),
            )
            if record:
                out = out + (
                    acc(c1, rise & lane_ok),
                    acc(c2, expired & lane_ok),
                    acc(c3, expired & seen & lane_ok),
                    acc(c4, off_now & lane_ok),
                )
            return out

        st = jax.lax.fori_loop(0, t_chunk, slot_body, st)
        # x partials of the whole tile in one MXU product: ones (8, BN)
        # against the on-tile's lanes gives (8, t_chunk) rows of lane
        # sums, exact (0/1 terms, at most BN of them, f32 accumulation)
        xs = jax.lax.dot_general(
            jnp.ones((8, bn), jnp.float32), on_scr[...],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        x_scr[slot] = xs[0:1, :].astype(jnp.int32)
        x_dma(slot, i).start()
        return st

    final = jax.lax.fori_loop(0, n_tiles, tile_body, init)

    # drain the in-flight x DMAs (at most the last two tiles')
    if n_tiles >= 2:
        x_dma((n_tiles - 2) % 2, n_tiles - 2).wait()
    x_dma((n_tiles - 1) % 2, n_tiles - 1).wait()

    so_ref[0, pl.ds(0, 1), :] = final[0]
    so_ref[0, pl.ds(1, 1), :] = final[2]
    ono_ref[pl.ds(0, 1), :] = final[1]
    for k, tot in enumerate(final[3:]):
        acc_ref[0, pl.ds(k, 1), :] = tot


def provision_scan_stream(
    traces: jax.Array,          # (B, T) int32 demand rows
    predicted: jax.Array,       # (R, T) int32 predicted rows the peek reads
    thresholds: jax.Array,      # (K, 1, N) constant or (K, T, N) sampled waits
    cell_trace: jax.Array,      # (G,) int32 demand row per cell
    cell_pred: jax.Array,       # (G,) int32 predicted row per cell
    cell_thr: jax.Array,        # (G,) int32 threshold-table row per cell
    cell_hor: jax.Array,        # (G,) int32 horizon-table row per cell
    *,
    horizon: int,               # peek slots unrolled: min(max_w+1, delta), 0 = none
    t_chunk: int = DEFAULT_T_CHUNK,
    n_levels: int | None = None,  # real level count for the x mask (default N)
    base_level: jax.Array | int = 0,
    routes: jax.Array | None = None,  # (N,) int32 routed level id per lane
    level_horizon: jax.Array | None = None,  # (H, N) per-level peek reach rows
    block_levels: int = DEFAULT_BN,
    interpret: bool | None = None,
    record: bool = False,
    carry: dict | None = None,  # {"r","on","wait"} each (G, N) — None = fresh
) -> tuple[jax.Array, dict, dict]:
    """Streaming provisioning scan: O(t_chunk · BN) working set, any T.

    The same per-cell slot semantics as :func:`provision_scan_grid`, but
    the demand/predicted rows (and the (K, T, N) wait tables of the
    randomized policies) stay in HBM (``pl.ANY``) and are streamed in
    ``t_chunk``-slot tiles with double-buffered async copies; each slot
    stores its masked on row into a ``(t_chunk, BN)`` VMEM tile, which is
    lane-summed once per tile (one MXU product) into x(t) partials that
    are DMA'd back out.  Instead of the on-matrix, the kernel
    returns what the engine actually reduces it to:

    - ``x`` (G, T) int32 — on-lane count per slot (lanes masked to
      ``routes < n_levels``, like the sharded path's lane mask);
    - ``acc`` — per-lane int32 totals (G, N): ``run`` (on-slots), ``up`` /
      ``down`` (toggle edges against the virtual x(0)=a(0) boundary; the
      forced x(T)=a(T) final off is the *caller's* adjustment, since only
      the caller knows whether this call ends the trace), plus the four
      provenance counters (:data:`repro.obs.provenance.COUNT_ORDER`) when
      ``record=True``;
    - ``carry`` — ``{"r", "on", "wait"}`` (G, N) per-lane engine state
      after the last slot.  Feed it back via ``carry=`` and the next call
      continues the trace bit-exactly: chunking a trace across calls and
      accumulating ``acc`` reproduces the monolithic call (property-gated
      in tests/test_streaming.py).

    ``T`` need not be a multiple of ``t_chunk`` — the pad tail freezes the
    carry; a ``t_chunk`` past ``T`` only pads the one tile
    (``provision_stream`` clamps it to the trace).  The compiled route
    rounds ``t_chunk`` up to a multiple of 128 (Mosaic moves rows in whole
    lane tiles); results never depend on it.
    The peek reads ``horizon`` extra slots of each predicted tile, so a
    chunk boundary never truncates the lookahead *within one call*; across
    calls the caller chooses where to split (``provision_stream`` streams
    whole traces in one call, so no peek ever straddles a split).
    """
    traces = jnp.asarray(traces, jnp.int32)
    predicted = jnp.asarray(predicted, jnp.int32)
    assert traces.ndim == 2 and predicted.ndim == 2, (traces.shape, predicted.shape)
    T = traces.shape[1]
    interpret = _resolve_interpret(interpret)
    if not interpret:
        # Mosaic slices a row tile only in whole 128-lane units
        t_chunk = -(-t_chunk // LANE) * LANE
    # peek lookahead past each tile, padded to whole lane tiles likewise
    p_ext = -(-horizon // LANE) * LANE
    thresholds = jnp.asarray(thresholds, jnp.float32)
    assert thresholds.ndim == 3, thresholds.shape
    time_varying = thresholds.shape[1] != 1
    if time_varying:
        assert thresholds.shape[1] == T, (thresholds.shape, T)
    n = thresholds.shape[-1]
    if n_levels is None:
        n_levels = n
    G = cell_trace.shape[0]
    bn = block_levels
    n_padded = -(-n // bn) * bn
    pad_n = n_padded - n
    n_tiles = -(-T // t_chunk)
    T_pad = n_tiles * t_chunk
    assert 0 <= horizon, horizon

    m3d = thresholds
    if level_horizon is None:
        h2d = jnp.full((1, n), float(horizon), jnp.float32)
    else:
        h2d = jnp.asarray(level_horizon, jnp.float32)
    if routes is None:
        routes = jnp.asarray(base_level, jnp.int32) + jnp.arange(n, dtype=jnp.int32)
    r2d = jnp.asarray(routes, jnp.int32).reshape(1, n)
    if carry is None:
        fresh = 1
        c_r = jnp.zeros((G, n), jnp.float32)
        c_on = jnp.zeros((G, n), jnp.int32)
        c_w = jnp.zeros((G, n), jnp.float32)
    else:
        fresh = 0
        c_r = jnp.asarray(carry["r"], jnp.float32)
        c_on = jnp.asarray(carry["on"]).astype(jnp.int32)
        c_w = jnp.asarray(carry["wait"], jnp.float32)
        assert c_r.shape == (G, n), (c_r.shape, (G, n))
    if pad_n:
        m3d = jnp.pad(m3d, ((0, 0), (0, 0), (0, pad_n)))
        h2d = jnp.pad(h2d, ((0, 0), (0, pad_n)))
        r2d = jnp.pad(r2d, ((0, 0), (0, pad_n)), constant_values=PAD_ROUTE)
        c_r = jnp.pad(c_r, ((0, 0), (0, pad_n)))
        c_on = jnp.pad(c_on, ((0, 0), (0, pad_n)))
        c_w = jnp.pad(c_w, ((0, 0), (0, pad_n)))
    if time_varying:
        m3d = jnp.pad(m3d, ((0, 0), (0, T_pad - T), (0, 0)))
    a_pad = jnp.pad(traces, ((0, 0), (0, T_pad - T)))[:, None, :]
    p_pad = jnp.pad(predicted, ((0, 0), (0, T_pad - T + p_ext)))[:, None, :]
    st_in = jnp.stack([c_r, c_w], axis=1)            # (G, 2, NP)
    # (rows, 1, NP) with a squeezed row dim: legal (1, BN) tiles per row
    h3d = h2d[:, None, :]
    c_on = c_on[:, None, :]
    cells = tuple(jnp.asarray(c, jnp.int32) for c in
                  (cell_trace, cell_pred, cell_thr, cell_hor))
    flags = jnp.asarray([fresh, n_levels], jnp.int32)
    n_acc = 7 if record else 3
    nblk = n_padded // bn

    kernel = functools.partial(
        _stream_scan_kernel, T=T, t_chunk=t_chunk, n_tiles=n_tiles, bn=bn,
        horizon=horizon, p_ext=p_ext, time_varying=time_varying,
        record=record,
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    m_spec = (
        any_spec if time_varying
        else pl.BlockSpec((1, 1, bn), lambda g, j, *p: (p[2][g], 0, j))
    )
    scratch = [
        pltpu.SMEM((2, 1, t_chunk), jnp.int32),            # a tiles
        pltpu.SMEM((2, 1, t_chunk + p_ext), jnp.int32),    # p tiles (+ lookahead)
        pltpu.VMEM((2, 1, t_chunk), jnp.int32),            # x partials out
        pltpu.VMEM((t_chunk, bn), jnp.float32),            # on rows of a tile
    ]
    if time_varying:
        scratch.append(pltpu.VMEM((2, t_chunk, bn), jnp.float32))
    scratch += [pltpu.SemaphoreType.DMA((2,))] * (4 if time_varying else 3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(G, nblk),
        in_specs=[
            any_spec,                                            # a
            any_spec,                                            # p
            m_spec,                                              # thresholds
            pl.BlockSpec((None, 1, bn), lambda g, j, *p: (p[3][g], 0, j)),  # hor.
            pl.BlockSpec((1, bn), lambda g, j, *p: (0, j)),        # routes
            pl.BlockSpec((1, 2, bn), lambda g, j, *p: (g, 0, j)),  # r/wait in
            pl.BlockSpec((None, 1, bn), lambda g, j, *p: (g, 0, j)),  # on in
        ],
        out_specs=[
            any_spec,                                              # x partials
            pl.BlockSpec((1, n_acc, bn), lambda g, j, *p: (g, 0, j)),
            pl.BlockSpec((1, 2, bn), lambda g, j, *p: (g, 0, j)),  # r/wait out
            pl.BlockSpec((None, 1, bn), lambda g, j, *p: (g, 0, j)),  # on out
        ],
        scratch_shapes=scratch,
    )
    x_part, acc, st_out, on_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((G, nblk, 1, T_pad), jnp.int32),
            jax.ShapeDtypeStruct((G, n_acc, n_padded), jnp.int32),
            jax.ShapeDtypeStruct((G, 2, n_padded), jnp.float32),
            jax.ShapeDtypeStruct((G, 1, n_padded), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="provision_scan_stream",
    )(*cells, flags, a_pad, p_pad, m3d, h3d, r2d, st_in, c_on)
    x = x_part.sum(axis=(1, 2))[:, :T].astype(jnp.int32)
    names = ("run", "up", "down")
    if record:
        names = names + ("demand_rise", "wait_expired", "peek_fired", "toggle_off")
    accs = {name: acc[:, k, :n] for k, name in enumerate(names)}
    carry_out = {
        "r": st_out[:, 0, :n],
        "on": on_out[:, 0, :n] != 0,
        "wait": st_out[:, 1, :n],
    }
    return x, accs, carry_out


def provision_scan(
    a: jax.Array,               # (T,) int32 demand per slot
    thresholds: jax.Array,      # (N,) constant waits or (T, N) sampled waits
    *,
    delta: int,                 # static pad/peek bound: ceil(max per-level Delta)
    horizon: int,               # peek slots unrolled: min(w+1, delta), 0 = no peek
    base_level: jax.Array | int = 0,
    predicted: jax.Array | None = None,   # (T,) trace the peek reads; default a
    level_horizon: jax.Array | None = None,  # (N,) per-level peek reach (slots)
    block_levels: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> jax.Array:
    """(T, N) bool on-matrix for levels [base_level, base_level + N).

    The single-cell convenience wrapper over :func:`provision_scan_grid`
    (one trace, one window, one noise level — ``G = 1``).
    """
    a = jnp.asarray(a, jnp.int32)
    thresholds = jnp.asarray(thresholds, jnp.float32)
    if thresholds.ndim == 2:
        m3d = thresholds[None]                      # (1, T, N)
    else:
        m3d = thresholds[None, None]                # (1, 1, N)
    pred = a if predicted is None else jnp.asarray(predicted, jnp.int32)
    lh = None if level_horizon is None else jnp.asarray(level_horizon)[None]
    zero = jnp.zeros((1,), jnp.int32)
    out = provision_scan_grid(
        a[None], pred[None], m3d, zero, zero, zero, zero,
        delta=delta, horizon=horizon, base_level=base_level,
        level_horizon=lh, block_levels=block_levels, interpret=interpret,
    )
    return out[0]
