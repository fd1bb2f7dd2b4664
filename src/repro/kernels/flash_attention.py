"""Flash attention Pallas TPU kernel (online softmax, no S x S
materialization).

Used by (a) the VAE mid-block (1 head over H*W <= 16,384 tokens) and (b)
the LM prefill path (GQA, causal, optional sliding window).  Layout:
q [n, hq, sq, d]; k, v [n, hkv, skv, d]; hq % hkv == 0 (GQA: the k/v
BlockSpec index maps a q-head program to its kv head, so no repeated k/v
materialization in HBM).

Grid (n*hq, sq_tiles, skv_tiles): the kv axis is innermost/sequential; the
output block and the fp32 (m, l, acc) running stats live in VMEM scratch
revisited across kv steps.  Causal/window masking is computed from the
absolute positions (q tiles are offset by skv - sq so q/k align at the
sequence end); fully-masked kv tiles are skipped via block-level early-out.  Both
matmuls run at full fp32 precision (the decode is held to the fp32 oracle).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: Optional[int],
               bq: int, bkv: int, q_off: int):
    kv_i = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
    k = k_ref[0].astype(jnp.float32)                  # [bkv, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)  # [bq, bkv]

    if causal or window is not None:
        q_pos = (pl.program_id(1) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 0)) + q_off
        k_pos = kv_i * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                               # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # [bq, bkv]
    corr = jnp.exp(m_prev - m_new)                    # [bq, 1]
    l_new = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v_ref[0].astype(jnp.float32), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kv_i == nkv - 1)
    def _finalize():
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)          # fully-masked rows -> 0
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "window",
                                             "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    window: Optional[int] = None, block_q: int = 128,
                    block_kv: int = 128, interpret: bool = False) -> jax.Array:
    n, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, "GQA requires hq % hkv == 0"
    rep = hq // hkv
    scale = float(d ** -0.5) if scale is None else float(scale)

    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    bkv = min(block_kv, skv)
    while skv % bkv:
        bkv //= 2
    grid = (n * hq, sq // bq, skv // bkv)
    q_off = skv - sq                                   # align at sequence end

    qf = q.reshape(n * hq, sq, d)
    kf = k.reshape(n * hkv, skv, d)
    vf = v.reshape(n * hkv, skv, d)

    def kv_index(h, i, j):
        return (h // rep, j, 0)

    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, q_off=q_off),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, d), kv_index),
            pl.BlockSpec((1, bkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, d), jnp.float32),    # fp32 accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(n, hq, sq, d)
