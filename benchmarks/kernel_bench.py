"""Kernel microbenchmarks (interpret-mode correctness timing on CPU; the
useful derived number is the achieved-vs-roofline arithmetic on TPU specs).

Runs as part of ``benchmarks/run.py`` or standalone::

    PYTHONPATH=src python benchmarks/kernel_bench.py           # all sections
    PYTHONPATH=src python benchmarks/kernel_bench.py --smoke   # long-trace
                                                               # section only,
                                                               # CI sizes

The long-trace section (:func:`provision_stream_long`) is the
production-length axis of the perf trajectory: the chunked double-buffered
streaming kernel against the monolithic prefetch-all grid kernel on an
overlapping size (bit-exact, asserted), then streaming-only rows at
T = 10^6 slots and a 10^4-lane fleet — sizes where the monolithic layout's
O(B·T) scalar prefetch is unrepresentable.  Each row carries the
per-slot decision latency and both layouts' working-set estimates, so the
memory win is explicit in BENCH.  ``--smoke`` shrinks T/N for CI; the keys
are stable either way and ``bench_diff.py`` treats all wall-clock columns
as informational, never gated.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import flash_attention_ref
from repro.launch.compile_cache import enable_compile_cache

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def _bench(fn, *args, iters=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def flash_roofline(rows: list[str]) -> None:
    """Analytic roofline occupancy for the flash kernel tiling."""
    for s, hd, bq, bk in ((4096, 128, 512, 512), (32768, 128, 512, 1024)):
        flops = 4 * s * s * hd / 2          # causal
        hbm = 3 * s * hd * 2 + s * hd * 2   # q,k,v read + o write (bf16)
        t_c = flops / PEAK_FLOPS
        t_m = hbm / HBM_BW
        ai = flops / hbm
        vmem = (bq * hd + 2 * bk * hd + bq * bk) * 4 + bq * (hd + 2) * 4
        rows.append(
            f"flash_roofline_s{s},0.0,"
            f"ai={ai:.0f};compute_us={t_c * 1e6:.1f};mem_us={t_m * 1e6:.1f};"
            f"vmem_bytes={vmem};bound={'compute' if t_c > t_m else 'memory'}"
        )


def decode_roofline(rows: list[str]) -> None:
    for s, kvh, hd, b in ((32768, 8, 128, 128), (524288, 5, 64, 1)):
        cache_bytes = 2 * b * s * kvh * hd * 2
        flops = 4 * b * s * kvh * hd  # q.k + p.v per kv head group
        t_m = cache_bytes / HBM_BW
        t_c = flops / PEAK_FLOPS
        rows.append(
            f"decode_roofline_s{s},0.0,"
            f"cache_gb={cache_bytes / 1e9:.2f};mem_us={t_m * 1e6:.1f};"
            f"compute_us={t_c * 1e6:.1f};bound=memory"
        )


def provision_grid_vs_lax_scan(rows: list[str]) -> None:
    """Batched (S, W, B) provisioning grid: the fused Pallas grid kernel
    (one program per (cell, level block), interpret mode off-TPU) against
    the vmapped lax.scan engine on identical cells — same A1 thresholds,
    same per-window peek horizons, bit-identical output (asserted)."""
    from repro.core.jax_provision import _on_matrix_scan
    from repro.kernels.provision_scan import provision_scan_grid

    S, W, B, T, N = 2, 3, 2, 256, 128
    delta, max_w = 6, 2
    rng = np.random.default_rng(0)
    ab = jnp.asarray(rng.integers(0, N, size=(B, T)), jnp.int32)
    pred = jnp.asarray(
        np.stack([rng.integers(0, N, size=(B, T)) for _ in range(S)]), jnp.int32
    ).reshape(S * B, T)
    windows = jnp.arange(W, dtype=jnp.float32)
    thr = jnp.broadcast_to(                                      # (W, 1, N)
        jnp.maximum(0.0, float(delta) - windows - 1.0)[:, None, None], (W, 1, N)
    )
    hor = jnp.broadcast_to(                                      # (W, N)
        jnp.minimum(windows + 1.0, float(delta))[:, None], (W, N)
    )
    s_ix, w_ix, b_ix = jnp.meshgrid(
        jnp.arange(S), jnp.arange(W), jnp.arange(B), indexing="ij"
    )
    cells = (
        b_ix.reshape(-1), (s_ix * B + b_ix).reshape(-1),
        w_ix.reshape(-1), w_ix.reshape(-1),
    )

    kernel_fn = jax.jit(lambda: provision_scan_grid(
        ab, pred, thr, *cells, delta=delta, horizon=max_w + 1,
        level_horizon=hor,
    ))

    levels = jnp.arange(N)

    def per_cell(bi, pi, wi):
        return _on_matrix_scan(
            ab[bi], pred[pi], levels, delta=float(delta), max_h=delta,
            window=windows[wi], policy="A1",
        )

    scan_fn = jax.jit(lambda: jax.vmap(per_cell)(cells[0], cells[1], cells[2]))

    got, want = kernel_fn(), scan_fn()
    assert (np.asarray(got) == np.asarray(want)).all(), "grid kernel != lax.scan"
    cells_n = S * W * B * T * N
    mode = "tpu" if jax.default_backend() == "tpu" else "interpret"
    for tag, fn in ((f"pallas_{mode}", kernel_fn), ("lax_scan", scan_fn)):
        us = _bench(fn)
        rows.append(
            f"provision_grid_{tag}_s{S}w{W}b{B}n{N},{us:.1f},"
            f"decisions_per_s={cells_n / (us / 1e6):.3e}"
        )


def provision_grid_routed(rows: list[str]) -> None:
    """Typed-fleet block packing: the same (W, B) grid through the kernel's
    group-aligned routed layout (scalar-prefetch route lanes, pad lanes
    carrying the sentinel id) vs the contiguous single-type layout — the
    routing must be pure lane relabeling, bit-identical after compaction."""
    from repro.core.jax_provision import _group_layout
    from repro.kernels.provision_scan import provision_scan_grid

    W, B, T = 2, 2, 256
    group_sizes = (24, 40)                        # d=2 typed fleet, n=64
    n = sum(group_sizes)
    delta, max_w = 6, 2
    rng = np.random.default_rng(1)
    ab = jnp.asarray(rng.integers(0, n, size=(B, T)), jnp.int32)
    windows = jnp.arange(W, dtype=jnp.float32)
    thr1 = jnp.maximum(0.0, float(delta) - windows - 1.0)        # (W,)
    hor1 = jnp.minimum(windows + 1.0, float(delta))              # (W,)
    w_ix, b_ix = jnp.meshgrid(jnp.arange(W), jnp.arange(B), indexing="ij")
    cells = (b_ix.reshape(-1), b_ix.reshape(-1),
             w_ix.reshape(-1), w_ix.reshape(-1))

    route_np, sel_np, n_layout = _group_layout(n, group_sizes, 1)
    sel = jnp.asarray(sel_np)
    thr_l = jnp.zeros((W, 1, n_layout)).at[:, :, sel].set(
        jnp.broadcast_to(thr1[:, None, None], (W, 1, n))
    )
    hor_l = jnp.zeros((W, n_layout)).at[:, sel].set(
        jnp.broadcast_to(hor1[:, None], (W, n))
    )

    contig = jax.jit(lambda: provision_scan_grid(
        ab, ab, jnp.broadcast_to(thr1[:, None, None], (W, 1, n)), *cells,
        delta=delta, horizon=max_w + 1,
        level_horizon=jnp.broadcast_to(hor1[:, None], (W, n)),
    ))
    routed = jax.jit(lambda: provision_scan_grid(
        ab, ab, thr_l, *cells, delta=delta, horizon=max_w + 1,
        level_horizon=hor_l, routes=jnp.asarray(route_np),
    ))

    got, want = np.asarray(routed())[..., sel_np], np.asarray(contig())
    assert (got == want).all(), "routed grid kernel != contiguous layout"
    cells_n = W * B * T
    mode = "tpu" if jax.default_backend() == "tpu" else "interpret"
    for tag, fn, lanes in ((f"contig_{mode}", contig, n),
                           (f"routed_{mode}", routed, n_layout)):
        us = _bench(fn)
        rows.append(
            f"provision_grid_{tag}_w{W}b{B}n{lanes},{us:.1f},"
            f"decisions_per_s={cells_n * lanes / (us / 1e6):.3e}"
        )


def interpret_correctness(rows: list[str]) -> None:
    """Tiny interpret-mode run vs oracle (wall time = CPU emulation only)."""
    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    us = _bench(
        lambda a, b, c: flash_attention(a, b, c, causal=True, block_q=128,
                                        block_k=128, interpret=True),
        q, k, v, iters=1,
    )
    err = float(
        jnp.max(jnp.abs(
            flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                            interpret=True)
            - flash_attention_ref(q, k, v, causal=True)
        ))
    )
    rows.append(f"flash_interpret_256,{us:.1f},max_err={err:.2e}")


def provision_stream_long(rows: list[str], *, full: bool = False) -> None:
    """Production-length traces through the chunked streaming kernel.

    One row per (T, N, layout): ``us_per_call`` plus ``decisions_per_s``,
    per-slot latency ``slot_ns`` and the working-set estimates
    ``mem_stream_bytes`` (2 trace tiles x double buffer, the (t_chunk, BN)
    on-tile + per-level carry) vs ``mem_monolithic_bytes`` (the
    prefetch-all layout's whole-trace residency and its on-matrix block)
    — O(T_chunk) against O(T).  The overlapping size runs both kernels and
    asserts bit-identical replica counts before timing.
    """
    from repro.kernels.provision_scan import (
        DEFAULT_BN,
        provision_scan_grid,
        provision_scan_stream,
    )

    t_chunk = 4096
    T_cmp = 65_536 if full else 8_192
    T_long = 1_000_000 if full else 65_536
    N_wide = 10_000 if full else 2_048
    N = 128
    delta, horizon = 6, 2
    mode = "tpu" if jax.default_backend() == "tpu" else "interpret"
    rng = np.random.default_rng(7)
    z = jnp.zeros((1,), jnp.int32)

    def mem(T, n, tc):
        # demand + predicted rows (int32): tiles x double buffer streaming,
        # plus the f32 on-tile the x partials are summed from; whole-trace
        # residency and the (T, BN) on-matrix block monolithic; carry is
        # per-level either way
        return (2 * 2 * tc * 4 + tc * DEFAULT_BN * 4 + 3 * n * 4,
                2 * T * 4 + T * DEFAULT_BN * 4)

    def stream_fn(a, thr, tc):
        return jax.jit(lambda a: provision_scan_stream(
            a, a, thr, z, z, z, z, horizon=horizon, t_chunk=tc)[0])

    # --- overlapping size: monolithic vs streaming, bit-exact then timed
    a = jnp.asarray(rng.integers(0, N, size=(1, T_cmp)), jnp.int32)
    thr = jnp.full((1, 1, N), float(delta) - 1.0, jnp.float32)
    mono = jax.jit(lambda a: provision_scan_grid(
        a, a, thr, z, z, z, z, delta=delta, horizon=horizon))
    strm = stream_fn(a, thr, t_chunk)
    x_mono = np.asarray(mono(a)).sum(-1)
    x_strm = np.asarray(strm(a))
    assert (x_strm == x_mono).all(), "streaming kernel != monolithic grid"
    m_s, m_m = mem(T_cmp, N, t_chunk)
    for tag, fn, m in ((f"mono_{mode}", mono, m_m),
                       (f"stream_{mode}", strm, m_s)):
        us = _bench(lambda: fn(a))
        rows.append(
            f"provision_long_{tag}_t{T_cmp}n{N},{us:.1f},"
            f"decisions_per_s={T_cmp * N / (us / 1e6):.3e};"
            f"slot_ns={us * 1e3 / T_cmp:.1f};trace_bytes={m}"
        )

    # --- streaming-only sizes the monolithic layout cannot hold
    for tag, T, n in ((f"stream_{mode}_long", T_long, N),
                      (f"stream_{mode}_wide", 8_192, N_wide)):
        a = jnp.asarray(rng.integers(0, n, size=(1, T)), jnp.int32)
        thr = jnp.full((1, 1, n), float(delta) - 1.0, jnp.float32)
        fn = stream_fn(a, thr, t_chunk)
        us = _bench(lambda: fn(a), iters=1)
        m_s, m_m = mem(T, n, t_chunk)
        rows.append(
            f"provision_long_{tag}_t{T}n{n},{us:.1f},"
            f"decisions_per_s={T * n / (us / 1e6):.3e};"
            f"slot_ns={us * 1e3 / T:.1f};"
            f"mem_stream_bytes={m_s};mem_monolithic_bytes={m_m}"
        )


def run(rows: list[str], *, long_full: bool = False) -> None:
    flash_roofline(rows)
    decode_roofline(rows)
    interpret_correctness(rows)
    provision_grid_vs_lax_scan(rows)
    provision_grid_routed(rows)
    provision_stream_long(rows, full=long_full)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="long-trace section only, CI-sized T/N")
    args = ap.parse_args(argv)
    enable_compile_cache()
    rows: list[str] = []
    if args.smoke:
        provision_stream_long(rows, full=False)
    else:
        run(rows, long_full=True)
    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    print(f"# {len(rows)} benchmark rows", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
