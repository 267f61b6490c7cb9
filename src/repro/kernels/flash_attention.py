"""Flash attention (causal / sliding-window, GQA) as a Pallas TPU kernel.

Streaming-softmax tiling designed for the TPU memory hierarchy:
  * grid = (B, H, S/BQ, S/BK); the innermost axis is "arbitrary" so the
    running max / sum / accumulator live in VMEM scratch across KV tiles.
  * every matmul is (BQ, hd) x (hd, BK) or (BQ, BK) x (BK, hd) with
    BQ/BK multiples of 128 and hd in {64, 128, 256} — MXU-aligned.
  * GQA: the k/v BlockSpec index map divides the head index, so KV tiles are
    fetched once per kv-head and reused by its query-head group.
  * causal: KV tiles strictly above the diagonal skip their compute via
    @pl.when; the diagonal tile is masked inline.

VMEM footprint per program: BQ*hd (q) + 2*BK*hd (k,v) + BQ*BK (scores)
+ BQ*(hd+2) fp32 scratch — e.g. BQ=BK=512, hd=128: ~1.9 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = float(-1e30)


def _flash_kernel(
    q_ref, k_ref, v_ref,       # (BQ, hd), (BK, hd), (BK, hd)
    o_ref,                     # (BQ, hd)
    m_scr, l_scr, acc_scr,     # VMEM scratch: (BQ,), (BQ,), (BQ, hd) fp32
    *, scale: float, causal: bool, window: int, bq: int, bk: int, n_kv: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    # Skip fully-masked tiles (above the diagonal / outside the window).
    # NOTE: @pl.when skips the compute but the tile was still prefetched;
    # a triangle-packed grid would also save the HBM fetch (perf lever).
    run = jnp.bool_(True)
    if causal:
        run = k_start <= q_start + bq - 1
    if window > 0:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                           # (BQ, BK)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)                     # (BQ,)
        p = jnp.exp(s - m_new[:, None])                     # (BQ, BK)
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,              # (B, S, H, hd)
    k: jax.Array,              # (B, S, KVH, hd)
    v: jax.Array,              # (B, S, KVH, hd)
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, hd = q.shape
    kvh = k.shape[2]
    rep = H // kvh
    scale = hd ** -0.5 if scale is None else scale
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_kv = S // bk

    grid = (B, H, S // bq, n_kv)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        bq=bq,
        bk=bk,
        n_kv=n_kv,
    )
    # layout: move heads next to batch so blocks are (1,1,BQ,hd)
    qt = q.transpose(0, 2, 1, 3)      # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)      # (B, KVH, S, hd)
    vt = v.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)  # back to (B, S, H, hd)
