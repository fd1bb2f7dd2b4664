"""Fused decode output epilogue: GN + SiLU + conv_out + clamp + uint8.

The last stage of the VAE decode — ``conv_out(silu(gn(x)))`` followed by
the serving-side clamp to [-1, 1] and quantization to displayable uint8 —
previously ran as three ops with the float32 image crossing HBM (and the
device boundary) at 4x the displayed bytes.  This kernel is the fused
res-block kernel of :mod:`repro.kernels.gn_silu_conv` with the quantize
epilogue (:func:`repro.kernels.conv3x3.banded_conv`, ``quantize=True``),
so the jitted decode's final write is the uint8 HWC image itself: 1/4 the
output traffic, 1/4 the device->host transfer, and pixel-cache entries
charged at their real (uint8) byte size.

Quantization is the paper's display mapping ``round((clip(y, -1, 1) + 1)
* 127.5)`` computed in fp32 — identical on the oracle and the kernel, so
the two can only differ where the conv accumulation itself differs
(tests bound that at +-1 LSB).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.conv3x3 import banded_conv
from repro.kernels.gn_silu import gn_coefficients


@functools.partial(jax.jit, static_argnames=("groups", "eps", "rows",
                                             "block_cout", "stats_tile",
                                             "interpret"))
def output_epilogue(x: jax.Array, scale: jax.Array, bias: jax.Array,
                    w: jax.Array, b: Optional[jax.Array] = None,
                    groups: int = 32, eps: float = 1e-6, rows: int = 32,
                    block_cout: int = 128, stats_tile: int = 1024,
                    interpret: bool = False,
                    w_scale: Optional[jax.Array] = None) -> jax.Array:
    """``quantize_u8(conv3x3(silu(group_norm(x))))`` fused.  x [N, H, W,
    Cin] NHWC, scale/bias [Cin], w [3, 3, Cin, Cout], b [Cout] ->
    uint8 [N, H, W, Cout]."""
    mean_c, mul_c = gn_coefficients(x, scale, groups, eps, stats_tile,
                                     interpret)
    return banded_conv(x, w, b, rows=rows, block_cout=block_cout,
                       interpret=interpret, w_scale=w_scale,
                       gn=(mean_c, mul_c, bias.astype(jnp.float32)),
                       quantize=True)
