"""Flash attention (causal / sliding-window) Pallas TPU kernel.

Grid: (B*H, S/BQ) — one (BQ, hd) query tile per step, online-softmax over
K/V tiles of BK rows held in VMEM.  Running max/sum/accumulator live in
VMEM scratch; K/V stream through a fori_loop with dynamic in-tile slices,
so VMEM holds O(BQ*hd + BK*hd) regardless of sequence length.  Causal and
window masks are applied per (BQ, BK) tile with absolute-position iota; for
sliding-window configs the K loop is *clipped* to the live window slab
(O(S*W) work instead of O(S^2) — the h2o-danube SWA path).

MXU alignment: BQ = BK = 128, head_dim padded to a lane multiple by the
caller (ops.flash_attention handles padding/unpadding).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128
BK = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, window,
                 seq_len: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale              # (BQ, hd)
    T = k_ref.shape[1]
    q_pos = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)

    # K-range this query tile can see (causal upper bound, window lower)
    hi = T if not causal else jnp.minimum((qi + 1) * BQ, T)
    lo = 0
    if window is not None:
        lo = jnp.maximum(qi * BQ + 1 - window, 0)
    lo_blk = (lo // BK) if window is not None else 0
    hi_blk = pl.cdiv(hi, BK)

    def body(kb, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(kb * BK, BK), :].astype(jnp.float32)  # (BK, hd)
        v = v_ref[0, pl.ds(kb * BK, BK), :].astype(jnp.float32)
        s = q @ k.T                                        # (BQ, BK)
        k_pos = kb * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        mask = k_pos < seq_len
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l_new

    hd = q.shape[-1]
    acc0 = jnp.zeros((BQ, hd), jnp.float32)
    m0 = jnp.full((BQ,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((BQ,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo_blk, hi_blk, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window=None,
                    interpret: bool = False, scale: float = None,
                    kv_len: int = None) -> jax.Array:
    """q: (BH, S, hd); k/v: (BH, T, hd). hd and S should be 128-aligned
    (ops.py pads); returns (BH, S, hd).

    ``scale``/``kv_len`` override the softmax scale and the true (unpadded)
    KV length when the caller padded hd or T.
    """
    BH, S, hd = q.shape
    T = k.shape[1]
    assert S % BQ == 0 and T % BK == 0, (S, T)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kv_len = kv_len if kv_len is not None else T
    grid = (BH, S // BQ)
    return pl.pallas_call(
        functools.partial(_attn_kernel, causal=causal, window=window,
                          seq_len=kv_len, scale=scale),
        grid=grid,
        in_specs=[pl.BlockSpec((1, BQ, hd), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, BQ, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
