"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each kernel in this package must match its oracle to float tolerance across
the shape/dtype sweeps in ``tests/test_kernels_*.py``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def group_norm_silu_ref(x: jax.Array, scale: jax.Array, bias: jax.Array,
                        groups: int = 32, eps: float = 1e-6) -> jax.Array:
    """GroupNorm (fp32 stats) + SiLU, NHWC."""
    n, h, w, c = x.shape
    xf = x.astype(jnp.float32).reshape(n, h * w, groups, c // groups)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = xf.var(axis=(1, 3), keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(n, h, w, c) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return (xf * jax.nn.sigmoid(xf)).astype(x.dtype)


def gn_silu_conv3x3_ref(x: jax.Array, scale: jax.Array, bias: jax.Array,
                        w: jax.Array, b: Optional[jax.Array] = None,
                        groups: int = 32, eps: float = 1e-6) -> jax.Array:
    """``conv3x3(silu(group_norm(x)))`` — oracle for the fused res-block
    kernel; composition of the two oracles keeps it bit-identical to the
    unfused decode path."""
    return conv3x3_ref(group_norm_silu_ref(x, scale, bias, groups, eps), w, b)


def upsample_conv3x3_ref(x: jax.Array, w: jax.Array,
                         b: Optional[jax.Array] = None) -> jax.Array:
    """``conv3x3(nearest_upsample_2x(x))`` — oracle for the fused
    upsampler kernel (and the XLA decode path: this IS the unfused
    upsample, so rewiring the decoder onto the dispatch is bit-neutral
    on ``impl='xla'``)."""
    x2 = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return conv3x3_ref(x2, w, b)


def quantize_u8_ref(y: jax.Array) -> jax.Array:
    """[-1, 1] float image -> uint8 display bytes (fp32 math)."""
    yf = jnp.clip(y.astype(jnp.float32), -1.0, 1.0)
    return jnp.round((yf + 1.0) * 127.5).astype(jnp.uint8)


def output_epilogue_ref(x: jax.Array, scale: jax.Array, bias: jax.Array,
                        w: jax.Array, b: Optional[jax.Array] = None,
                        groups: int = 32, eps: float = 1e-6) -> jax.Array:
    """``quantize_u8(conv3x3(silu(group_norm(x))))`` — oracle for the
    fused decode output epilogue."""
    return quantize_u8_ref(gn_silu_conv3x3_ref(x, scale, bias, w, b,
                                               groups, eps))


def rms_norm_ref(x: jax.Array, scale: jax.Array, bias: jax.Array,
                 eps: float = 1e-5) -> jax.Array:
    """RMSNorm over channels (NHWC last axis) with an affine bias, fp32
    statistics: ``x * rsqrt(mean(x^2) + eps) * scale + bias``."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def rms_output_epilogue_ref(x: jax.Array, scale: jax.Array,
                            bias: jax.Array, w: jax.Array,
                            b: Optional[jax.Array] = None,
                            eps: float = 1e-5) -> jax.Array:
    """``quantize_u8(conv3x3(relu(rms_norm(x))))`` — oracle for the DC-AE
    decode's uint8 output path."""
    y = jnp.maximum(rms_norm_ref(x, scale, bias, eps), 0.0)
    return quantize_u8_ref(conv3x3_ref(y, w, b))


def linear_attention_ref(sources, head_dim: int,
                         eps: float = 1e-15) -> jax.Array:
    """ReLU linear attention over packed part-major sources (see
    :mod:`repro.kernels.linear_attention`): each ``[N, T, 3 * Cq]`` source
    holds every head's q, then k, then v, ``head_dim`` channels a head;
    the result concatenates the sources' heads, ``[N, T, S * Cq]``."""
    hi = jax.lax.Precision.HIGHEST
    outs = []
    for x in sources:
        n, t, c3 = x.shape
        q, k, v = (jnp.maximum(a, 0.0) if i < 2 else a
                   for i, a in enumerate(
                       x.astype(jnp.float32).reshape(n, t, 3, -1, head_dim)
                       .transpose(2, 0, 1, 3, 4)))
        kv = jnp.einsum("nthd,nthe->nhde", v, k, precision=hi)
        num = jnp.einsum("nthe,nhde->nthd", q, kv, precision=hi)
        den = jnp.einsum("nthe,nhe->nth", q, k.sum(axis=1), precision=hi)
        outs.append((num / (den[..., None] + eps)).reshape(n, t, c3 // 3))
    return jnp.concatenate(outs, axis=-1).astype(sources[0].dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Softmax attention.  q: [n, hq, sq, d]; k, v: [n, hkv, skv, d].

    hq must be a multiple of hkv (GQA broadcast).  ``window`` enables
    sliding-window masking (attend to the last ``window`` positions),
    assuming q/k positions align at the sequence end (sq == skv for the
    windowed case)."""
    n, hq, sq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("nhqd,nhkd->nhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    skv = k.shape[2]
    if causal or window is not None:
        qpos = jnp.arange(sq)[:, None] + (skv - sq)
        kpos = jnp.arange(skv)[None, :]
        mask = jnp.ones((sq, skv), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("nhqk,nhkd->nhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         lengths: jax.Array,
                         scale: Optional[float] = None) -> jax.Array:
    """Single-token decode attention against a KV cache.

    q: [n, hq, d]; k_cache/v_cache: [n, hkv, S, d]; lengths: [n] valid
    prefix lengths.  Returns [n, hq, d]."""
    n, hq, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    kc = jnp.repeat(k_cache, rep, axis=1) if rep > 1 else k_cache
    vc = jnp.repeat(v_cache, rep, axis=1) if rep > 1 else v_cache
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("nhd,nhsd->nhs", q.astype(jnp.float32),
                        kc.astype(jnp.float32)) * scale
    mask = jnp.arange(s)[None, None, :] < lengths[:, None, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("nhs,nhsd->nhd", p, vc.astype(jnp.float32)).astype(q.dtype)


def conv3x3_ref(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None
                ) -> jax.Array:
    """3x3 SAME conv, NHWC x HWIO -> NHWC."""
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def rwkv6_scan_ref(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                   u: jax.Array, state: Optional[jax.Array] = None):
    """RWKV-6 linear-attention recurrence (per head), fp32 state.

    r, k, v, w: [n, h, t, d]; u: [h, d].  State S: [n, h, d, d] with
        out_t = r_t · (S + u ⊙ (k_t ⊗ v_t))
        S     = diag(exp(-exp(w_t))) S + k_t ⊗ v_t
    Returns (out [n, h, t, d], final_state).
    """
    n, h, t, d = r.shape
    if state is None:
        state = jnp.zeros((n, h, d, d), jnp.float32)
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    decay = jnp.exp(-jnp.exp(w.astype(jnp.float32)))          # [n,h,t,d]
    uf = u.astype(jnp.float32)

    def step(S, inputs):
        r_t, k_t, v_t, dec_t = inputs                          # [n,h,d]
        kv = k_t[..., :, None] * v_t[..., None, :]             # [n,h,d,d]
        out = jnp.einsum("nhd,nhde->nhe", r_t, S + uf[None, :, :, None] * kv)
        S = dec_t[..., :, None] * S + kv
        return S, out

    xs = (jnp.moveaxis(rf, 2, 0), jnp.moveaxis(kf, 2, 0),
          jnp.moveaxis(vf, 2, 0), jnp.moveaxis(decay, 2, 0))
    final, outs = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(outs, 0, 2).astype(r.dtype), final
