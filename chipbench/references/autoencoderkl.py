"""Plain reference of the AutoencoderKL decoder (the VAE of Stable
Diffusion 1.5, SD3.5 and FLUX.1), written from the published architecture
(diffusers ``Decoder``) in straightforward ``jax.numpy``.

    z -> z / scaling_factor + shift_factor -> [post_quant_conv 1x1]
      -> conv_in 3x3 -> mid (res, attn, res)
      -> up levels, top width first: layers_per_block + 1 res blocks,
         then nearest 2x + conv 3x3 (all but the last level)
      -> GroupNorm + SiLU -> conv_out 3x3 -> image in [-1, 1]

A res block is ``x + conv2(silu(gn2(conv1(silu(gn1(x))))))`` with a 1x1
shortcut conv where the widths differ; the mid attention is one head over
the latent grid, GroupNorm in front, ``softmax(q k^T / sqrt(C)) v`` and an
output projection, added to its input.  GroupNorm uses eps 1e-6 with
fp32 statistics.  Served bytes are ``round((clip(y, -1, 1) + 1) * 127.5)``.

Layout: NHWC activations, HWIO conv weights, ``[in, out]`` dense
weights.  Parameters are the benchmark's own (``chipbench/lib/weights``),
never read from the system under test.  ``precision="float32"`` runs
every product at full fp32 (``Precision.HIGHEST``); ``"bfloat16"`` holds
weights and activations in bf16 with default products — the control.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

EPS = 1e-6


def _settings(precision: str):
    if precision == "float32":
        return jnp.float32, jax.lax.Precision.HIGHEST
    if precision == "bfloat16":
        return jnp.bfloat16, jax.lax.Precision.DEFAULT
    raise ValueError(f"precision must be float32 or bfloat16: {precision!r}")


def _conv(x, p, dtype, prec):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(dtype), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
        preferred_element_type=jnp.float32)
    return (y + p["b"].astype(jnp.float32)).astype(dtype)


def _group_norm(x, p, groups, dtype):
    n, h, w, c = x.shape
    xf = x.astype(jnp.float32).reshape(n, h * w, groups, c // groups)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = jnp.square(xf - mean).mean(axis=(1, 3), keepdims=True)
    xf = ((xf - mean) / jnp.sqrt(var + EPS)).reshape(n, h, w, c)
    xf = xf * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return xf.astype(dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _resnet(x, p, groups, dtype, prec):
    h = _conv(_silu(_group_norm(x, p["norm1"], groups, dtype)), p["conv1"],
              dtype, prec)
    h = _conv(_silu(_group_norm(h, p["norm2"], groups, dtype)), p["conv2"],
              dtype, prec)
    if "shortcut" in p:
        x = _conv(x, p["shortcut"], dtype, prec)
    return x + h


def _dense(x, p, dtype, prec):
    y = jnp.einsum("ntc,cd->ntd", x, p["w"].astype(dtype), precision=prec,
                   preferred_element_type=jnp.float32)
    return (y + p["b"].astype(jnp.float32)).astype(dtype)


def _attention(x, p, groups, dtype, prec):
    n, h, w, c = x.shape
    y = _group_norm(x, p["norm"], groups, dtype).reshape(n, h * w, c)
    q = _dense(y, p["q"], dtype, prec)
    k = _dense(y, p["k"], dtype, prec)
    v = _dense(y, p["v"], dtype, prec)
    logits = jnp.einsum("nqc,nkc->nqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(c)
    attn = jax.nn.softmax(logits, axis=-1).astype(dtype)
    o = jnp.einsum("nqk,nkc->nqc", attn, v, precision=prec,
                   preferred_element_type=jnp.float32).astype(dtype)
    o = _dense(o, p["proj"], dtype, prec)
    return x + o.reshape(n, h, w, c)


def _upsample(x, p, dtype, prec):
    x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return _conv(x, p["conv"], dtype, prec)


def decode(params: Dict[str, Any], z: jax.Array, arch: Dict[str, Any],
           precision: str = "float32") -> jax.Array:
    """latent [N, h, w, C] -> float image [N, 8h, 8w, 3] (fp32)."""
    dtype, prec = _settings(precision)
    groups = arch["norm_num_groups"]
    x = z.astype(jnp.float32) / arch["scaling_factor"]
    x = (x + (arch.get("shift_factor") or 0.0)).astype(dtype)
    if arch.get("use_post_quant_conv"):
        x = _conv(x, params["post_quant_conv"], dtype, prec)
    x = _conv(x, params["conv_in"], dtype, prec)
    x = _resnet(x, params["mid"]["res1"], groups, dtype, prec)
    x = _attention(x, params["mid"]["attn"], groups, dtype, prec)
    x = _resnet(x, params["mid"]["res2"], groups, dtype, prec)
    for level in params["up"]:
        for blk in level["blocks"]:
            x = _resnet(x, blk, groups, dtype, prec)
        if "upsample" in level:
            x = _upsample(x, level["upsample"], dtype, prec)
    x = _silu(_group_norm(x, params["norm_out"], groups, dtype))
    return _conv(x, params["conv_out"], dtype, prec).astype(jnp.float32)


def to_u8(y: jax.Array) -> jax.Array:
    """[-1, 1] float image -> served display bytes."""
    y = jnp.clip(y.astype(jnp.float32), -1.0, 1.0)
    return jnp.round((y + 1.0) * 127.5).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def compiled(arch_items: tuple, precision: str):
    """The jitted reference for one architecture and precision:
    (params, latents [N, h, w, C]) -> uint8 images."""
    arch = dict(arch_items)
    return jax.jit(lambda p, z: to_u8(decode(p, z, arch, precision)))
