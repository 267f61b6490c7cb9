"""Single-token decode attention over a long KV cache (Pallas TPU kernel).

Decode attention is memory-bound: the whole cache streams HBM -> VMEM once
per token.  The kernel tiles the cache along sequence (BK) and keeps the
query-head group for one KV head resident:

  grid = (B, KVH, S/BK); innermost "arbitrary" so running max/sum/acc for
  the (rep, hd) group live in VMEM scratch across cache tiles.

Per-sequence ``lengths`` masks unwritten slots, so ragged batches (paper-
style sessions pinned to replicas) decode without repacking.

VMEM per program: rep*hd (q) + 2*BK*hd (k,v tiles) + rep*(hd+2) scratch —
BK=1024, hd=128, rep=8: ~0.8 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 1024
NEG_INF = float(-1e30)


def _decode_kernel(
    len_ref,                    # scalar prefetch: (B,) int32
    q_ref, k_ref, v_ref,        # (1, 1, rep, hd), (1, BK, 1, hd), (1, BK, 1, hd)
    o_ref,                      # (1, 1, rep, hd)
    m_scr, l_scr, acc_scr,      # (rep,), (rep,), (rep, hd) fp32
    *, scale: float, bk: int, n_kv: int, rep: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    k_start = ki * bk

    @pl.when(k_start < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                  # (rep, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)            # (BK, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                            # (rep, BK)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rep, bk), 1)
        mask = k_pos < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, :, 0, :].astype(jnp.float32)            # (BK, hd)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,               # (B, H, hd)
    k_cache: jax.Array,         # (B, S, KVH, hd)
    v_cache: jax.Array,         # (B, S, KVH, hd)
    lengths: jax.Array,         # (B,) int32
    *,
    scale: float | None = None,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    B, S, kvh, hd = k_cache.shape
    H = q.shape[1]
    rep = H // kvh
    scale = hd ** -0.5 if scale is None else scale
    bk = min(block_k, S)
    assert S % bk == 0, (S, bk)
    n_kv = S // bk

    qg = q.reshape(B, kvh, rep, hd)

    kernel = functools.partial(
        _decode_kernel, scale=scale, bk=bk, n_kv=n_kv, rep=rep
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, kvh, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, j, lens: (b, j, h, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, j, lens: (b, j, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)
    return out.reshape(B, H, hd)
