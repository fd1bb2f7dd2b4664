"""Fused GroupNorm + SiLU Pallas TPU kernel.

The VAE decoder applies GN+SiLU before every conv — at 1024x1024 output the
activations dominate HBM traffic, so fusing the normalize+affine+activation
into one VMEM pass halves the memory term of the decode roofline vs
unfused GN / SiLU (traffic rows in ``benchmarks/bench_kernels.py``; the
decode path itself now goes one step further and fuses the trailing conv
too — see :mod:`repro.kernels.gn_silu_conv`).

Two-pass structure (stats must exist before scaling):
  pass 1  grid (N, T): per-spatial-tile per-channel partial sums ->
          (sum, sumsq) [N, 1, C], accumulated across the T axis by
          revisiting the output block; the [N, G] group statistics and
          their per-channel broadcast are a few hundred bytes of XLA in
          the wrapper (:func:`gn_coefficients`) — splitting channels into
          groups on the lane axis inside the kernel is a shape cast Mosaic
          cannot lower;
  pass 2  grid (N, T): y = silu((x - mean_c) * mul_c + bias) with the
          per-channel ``mean_c`` and ``mul_c = rsqrt(var + eps) * scale``.

Blocks keep channels whole on the lane axis and tile the fused spatial
axis; fp32 statistics regardless of input dtype.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.conv3x3 import compiler_params

#: Bytes of one stats/apply input block the spatial tile is sized to.
TILE_BYTES = 2 * 2 ** 20


def _channel_sums_kernel(x_ref, sum_ref, sq_ref):
    x = x_ref[0].astype(jnp.float32)                 # [tile, C]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    sum_ref[0] += jnp.sum(x, axis=0, keepdims=True)
    sq_ref[0] += jnp.sum(x * x, axis=0, keepdims=True)


def _spatial_tile(hw: int, c: int, tile: int) -> int:
    tile = min(tile, hw)
    while tile > 8 and (hw % tile or tile * max(c, 128) * 4 > TILE_BYTES):
        tile //= 2
    while hw % tile:
        tile //= 2
    return tile


def gn_coefficients(x: jax.Array, scale: jax.Array, groups: int,
                    eps: float, tile: int = 1024, interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """GroupNorm statistics of ``x`` [N, H, W, C] as per-image,
    per-channel ``(mean_c, mul_c)``, each [N, 1, C] fp32, where
    ``mul_c = rsqrt(var + eps) * scale`` — the pass-1 output every fused
    GN kernel consumes."""
    n, h, w, c = x.shape
    hw = h * w
    xf = x.reshape(n, hw, c)
    tile = _spatial_tile(hw, c, tile)
    stats_shape = jax.ShapeDtypeStruct((n, 1, c), jnp.float32)
    sums, sqs = pl.pallas_call(
        _channel_sums_kernel,
        grid=(n, hw // tile),
        in_specs=[pl.BlockSpec((1, tile, c), lambda i, t: (i, t, 0))],
        out_specs=[pl.BlockSpec((1, 1, c), lambda i, t: (i, 0, 0)),
                   pl.BlockSpec((1, 1, c), lambda i, t: (i, 0, 0))],
        out_shape=[stats_shape, stats_shape],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(xf)
    cpg = c // groups
    count = float(hw * cpg)
    mean = sums.reshape(n, groups, cpg).sum(-1) / count      # [N, G]
    var = jnp.maximum(sqs.reshape(n, groups, cpg).sum(-1) / count
                      - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    mean_c = jnp.repeat(mean, cpg, axis=1)[:, None, :]
    mul_c = (jnp.repeat(inv, cpg, axis=1)
             * scale.astype(jnp.float32))[:, None, :]
    return mean_c, mul_c


def _apply_kernel(x_ref, mean_ref, mul_ref, bias_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)                 # [tile, C]
    y = (x - mean_ref[0]) * mul_ref[0] + bias_ref[...].astype(jnp.float32)
    o_ref[0] = (y * jax.nn.sigmoid(y)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "eps", "tile",
                                             "interpret"))
def group_norm_silu(x: jax.Array, scale: jax.Array, bias: jax.Array,
                    groups: int = 32, eps: float = 1e-6, tile: int = 1024,
                    interpret: bool = False) -> jax.Array:
    n, h, w, c = x.shape
    hw = h * w
    mean_c, mul_c = gn_coefficients(x, scale, groups, eps, tile, interpret)
    tile = _spatial_tile(hw, c, tile)
    y = pl.pallas_call(
        _apply_kernel,
        grid=(n, hw // tile),
        in_specs=[
            pl.BlockSpec((1, tile, c), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, 1, c), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((1, c), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile, c), lambda i, t: (i, t, 0)),
        out_shape=jax.ShapeDtypeStruct((n, hw, c), x.dtype),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(x.reshape(n, hw, c), mean_c, mul_c, bias.reshape(1, c))
    return y.reshape(n, h, w, c)
