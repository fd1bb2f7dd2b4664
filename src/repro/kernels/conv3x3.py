"""3x3 SAME conv as implicit GEMM — the decode path's dominant FLOP source.

TPU-native formulation (not an im2col port): for each output row-band the
kernel holds an input band + 1-row halo in VMEM and, one output row at a
time, accumulates nine (W, Cin) x (Cin, Cout-tile) MXU matmuls — one per
filter tap — shifted in the spatial dims.  Channels stay on the lane axis;
Cout tiles are 128-aligned for the MXU, and every bias/scale operand is a
lane-aligned ``(1, C)`` row.

Overlapping halo reads don't fit disjoint BlockSpec tiling, so the wrapper
materializes the row bands (with halo) once in HBM — an extra 2/rows of
input traffic — and the kernel itself then streams disjoint blocks.

:func:`banded_conv` is the one implementation behind three ops: the plain
conv (:func:`conv3x3`), the fused GroupNorm + SiLU + conv of the res
blocks (:mod:`repro.kernels.gn_silu_conv`, ``gn=`` set) and the decode's
uint8 output epilogue (:mod:`repro.kernels.output_epilogue`,
``quantize=True``).  Band height is sized against the whole per-step VMEM
footprint (:func:`step_vmem_bytes`), and the kernel asks Mosaic for
:data:`VMEM_LIMIT` of scoped VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Per-grid-step VMEM footprint the band sizing aims under.
VMEM_BUDGET = 32 * 2 ** 20
#: Scoped VMEM Mosaic may use per kernel (v5e has 128 MiB per core; the
#: default scoped limit is 16 MiB).  Headroom over the budget covers
#: Mosaic's own temporaries (row patches, accumulators).
VMEM_LIMIT = 64 * 2 ** 20

HIGHEST = jax.lax.Precision.HIGHEST


def compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def _pad(v: int, m: int) -> int:
    return -(-v // m) * m


def step_vmem_bytes(rows: int, width: int, cin: int, itemsize: int,
                    tc: int = 128, taps: int = 9, out_factor: int = 1,
                    fused_gn: bool = False) -> int:
    """VMEM one grid step holds: the double-buffered input band (lanes
    padded to 128, sublanes to 8), the fp32 normalized band scratch of
    the fused GN kernels, the double-buffered weight tile and the
    double-buffered fp32 output block (``out_factor`` output rows per
    band row: 2 for the upsampler's two row phases)."""
    band = (rows + 2) * _pad(width + 2, 8) * _pad(cin, 128)
    out = out_factor * rows * _pad(width, 8) * _pad(tc, 128) * 4
    wts = taps * _pad(cin, 8) * _pad(tc, 128) * 4
    total = 2 * band * itemsize + 2 * wts + 2 * out
    if fused_gn:
        total += band * 4
    return total


def band_rows(h: int, width: int, cin: int, itemsize: int, rows: int,
              **footprint) -> int:
    """Largest halving of ``rows`` that divides ``h`` AND whose per-step
    VMEM footprint (:func:`step_vmem_bytes`, extra keywords forwarded)
    fits :data:`VMEM_BUDGET` — shared by every banded kernel so the
    sizing policy can't drift."""
    rows = min(rows, h)
    while rows > 1 and (h % rows
                        or step_vmem_bytes(rows, width, cin, itemsize,
                                           **footprint) > VMEM_BUDGET):
        rows //= 2
    return rows


def cout_tile(cout: int, block_cout: int) -> int:
    tc = min(block_cout, cout)
    while cout % tc:
        tc //= 2
    return tc


def materialize_bands(x: jax.Array, rows: int) -> jax.Array:
    """[N, H, W, C] -> flattened row bands with 1-pixel halo
    [N * H/rows, rows+2, W+2, C] (the overlapping halo reads don't fit
    disjoint BlockSpec tiling, so the bands are staged once in HBM)."""
    n, h, width, cin = x.shape
    nb = h // rows
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    bands = jnp.stack([xp[:, i * rows:i * rows + rows + 2]
                       for i in range(nb)], axis=1)
    return bands.reshape(n * nb, rows + 2, width + 2, cin)


def tap_dot(patch: jax.Array, tap: jax.Array) -> jax.Array:
    """One filter tap: (P, Cin) x (Cin, tc) at full fp32 precision (the
    decode is held to the fp32 oracle within ±1 LSB)."""
    return jax.lax.dot_general(
        patch.astype(jnp.float32), tap.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)


def quantize_u8(y: jax.Array) -> jax.Array:
    """[-1, 1] float image -> uint8, the serving display mapping (the
    int32 hop is the cast path Mosaic lowers)."""
    yf = jnp.clip(y.astype(jnp.float32), -1.0, 1.0)
    return jnp.round((yf + 1.0) * 127.5).astype(jnp.int32).astype(jnp.uint8)


def _conv_kernel(*refs, rows: int, width: int, nb: int, fused_gn: bool,
                 has_scale: bool, quantize: bool):
    it = iter(refs)
    x_ref = next(it)                                 # [1, rows+2, W+2, Cin]
    if fused_gn:
        mean_ref, mul_ref, gbias_ref = next(it), next(it), next(it)
    w_ref = next(it)                                 # [3, 3, Cin, tc]
    s_ref = next(it) if has_scale else None          # [1, tc]
    b_ref, o_ref = next(it), next(it)                # [1, tc], [1, rows, W, tc]

    if fused_gn:
        ybuf = next(it)                              # [rows+2, W+2, Cin] f32
        band = pl.program_id(0) % nb

        # normalize + SiLU the band once per band (the Cout-tile axis is
        # the inner grid axis, so later tiles reuse the scratch)
        @pl.when(pl.program_id(1) == 0)
        def _normalize():
            cin = ybuf.shape[-1]
            col = jax.lax.broadcasted_iota(jnp.int32, (width + 2, cin), 0)
            interior = (col >= 1) & (col <= width)
            mean, mul = mean_ref[0], mul_ref[0]      # [1, Cin]
            gbias = gbias_ref[...].astype(jnp.float32)

            def norm_row(j, carry):
                y = (x_ref[0, j].astype(jnp.float32) - mean) * mul + gbias
                y = y * jax.nn.sigmoid(y)
                # the conv's SAME padding ring must stay zero after the
                # activation (silu(gn(0)) != 0): columns 0 and W+1, and
                # the outer halo row of an image's first and last band
                ring = (((j == 0) & (band == 0))
                        | ((j == rows + 1) & (band == nb - 1)))
                ybuf[j] = jnp.where(interior & jnp.logical_not(ring), y, 0.0)
                return carry

            jax.lax.fori_loop(0, rows + 2, norm_row, 0)

        def load(r, dx):
            return ybuf[r, pl.ds(dx, width), :]
    else:
        def load(r, dx):
            return x_ref[0, r, pl.ds(dx, width), :]

    bias = b_ref[...].astype(jnp.float32)
    scale = None if s_ref is None else s_ref[...].astype(jnp.float32)

    def conv_row(r, carry):
        acc = None
        for dy in range(3):
            for dx in range(3):
                d = tap_dot(load(r + dy, dx), w_ref[dy, dx])
                acc = d if acc is None else acc + d
        if scale is not None:
            # scale is per output channel, so one fp32 multiply of the
            # summed accumulator dequantizes all nine taps exactly
            acc = acc * scale
        y = acc + bias
        o_ref[0, r] = quantize_u8(y) if quantize else y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows, conv_row, 0)


def banded_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array], *,
                rows: int, block_cout: int, interpret: bool,
                w_scale: Optional[jax.Array] = None,
                gn: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
                quantize: bool = False) -> jax.Array:
    """x [N, H, W, Cin], w [3, 3, Cin, Cout] -> [N, H, W, Cout] (SAME).

    ``gn = (mean_c, mul_c, bias_c)`` normalizes + SiLU-activates the band
    in VMEM first (per-image ``[N, 1, Cin]`` mean and ``inv_std * scale``
    from :func:`repro.kernels.gn_silu.gn_coefficients`, ``[Cin]`` bias);
    ``quantize`` emits uint8 display pixels instead of ``x.dtype``."""
    n, h, width, cin = x.shape
    cout = w.shape[-1]
    if b is None:
        b = jnp.zeros((cout,), x.dtype)
    tc = cout_tile(cout, block_cout)
    rows = band_rows(h, width, cin, x.dtype.itemsize, rows, tc=tc,
                     fused_gn=gn is not None)
    nb = h // rows

    in_specs = [pl.BlockSpec((1, rows + 2, width + 2, cin),
                             lambda i, c: (i, 0, 0, 0))]
    operands = [materialize_bands(x, rows)]
    scratch = []
    if gn is not None:
        mean_c, mul_c, gbias = gn
        in_specs += [pl.BlockSpec((1, 1, cin), lambda i, c: (i // nb, 0, 0)),
                     pl.BlockSpec((1, 1, cin), lambda i, c: (i // nb, 0, 0)),
                     pl.BlockSpec((1, cin), lambda i, c: (0, 0))]
        operands += [mean_c, mul_c, gbias.reshape(1, cin)]
        scratch.append(pltpu.VMEM((rows + 2, width + 2, cin), jnp.float32))
    in_specs.append(pl.BlockSpec((3, 3, cin, tc), lambda i, c: (0, 0, 0, c)))
    operands.append(w)
    if w_scale is not None:
        in_specs.append(pl.BlockSpec((1, tc), lambda i, c: (0, c)))
        operands.append(w_scale.reshape(1, cout))
    in_specs.append(pl.BlockSpec((1, tc), lambda i, c: (0, c)))
    operands.append(b.reshape(1, cout))

    out = pl.pallas_call(
        functools.partial(_conv_kernel, rows=rows, width=width, nb=nb,
                          fused_gn=gn is not None,
                          has_scale=w_scale is not None, quantize=quantize),
        grid=(n * nb, cout // tc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, width, tc),
                               lambda i, c: (i, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct(
            (n * nb, rows, width, cout),
            jnp.uint8 if quantize else x.dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*operands)
    return out.reshape(n, h, width, cout)


@functools.partial(jax.jit, static_argnames=("rows", "block_cout", "interpret"))
def conv3x3(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
            rows: int = 32, block_cout: int = 128,
            interpret: bool = False,
            w_scale: Optional[jax.Array] = None) -> jax.Array:
    """x [N, H, W, Cin], w [3, 3, Cin, Cout] -> [N, H, W, Cout] (SAME).

    ``w`` may be stored float32/bfloat16 (cast to fp32 per tap tile) or
    int8 with ``w_scale`` [Cout] — the per-channel dequant then happens on
    the accumulator in VMEM, never as an fp32 weight copy in HBM."""
    return banded_conv(x, w, b, rows=rows, block_cout=block_cout,
                       interpret=interpret, w_scale=w_scale)
