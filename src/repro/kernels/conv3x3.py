"""3x3 SAME conv as implicit GEMM — the decode path's dominant FLOP source.

TPU-native formulation (not an im2col port): for each output row-band the
kernel holds an input band + 1-row halo in VMEM and accumulates shifted
(W, Cin) x (Cin, Cout-tile) MXU matmuls.  Channels stay on the lane axis;
Cout tiles are 128-aligned for the MXU, and every bias/scale operand is a
lane-aligned ``(1, C)`` row.

A band of an even number of rows runs Winograd minimal filtering F(2,3)
along the row axis: each pair of output rows takes four transformed input
rows (``d0-d2, d1+d2, d2-d1, d1-d3``) times three column taps of the
row-transformed filter (:func:`winograd_weights`), 12 matmuls where the
direct loop takes 2 x 9 = 18.  The transforms use only +-1 and 1/2 and the
products stay fp32 at ``Precision.HIGHEST``, so the result differs from
the direct sum by fp32 rounding alone.  A band of odd height (one row,
where two do not fit VMEM) keeps the direct nine-tap loop.  The column
taps stay unaligned ``pl.ds(dx, W)`` loads on either path.

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

Two more epilogues serve the DC-AE ResBlock ``x + RMSNorm(conv2(SiLU(
conv1(x))))`` (:func:`repro.vae.dcae.res_block`), so neither its SiLU nor
its RMSNorm and residual take a pass of their own over HBM:

* ``act="silu"`` writes ``y * sigmoid(y)`` after the bias (``silu(0) =
  0``, so the next conv's zero SAME ring holds);
* ``rms=(scale, bias)`` with ``residual`` writes ``residual + (y *
  rsqrt(mean_c(y^2) + eps)) * scale + bias``, fp32 statistics over every
  output channel.  The output block then spans all of Cout and stays in
  VMEM across the inner Cout-tile grid axis: each tile step stores its
  lane slice, and the last one normalizes each row of the block in place
  and adds the residual block, indexed like the output.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

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
                    fused_gn: bool = False, rms_cout: int = 0) -> int:
    """VMEM one grid step holds: the double-buffered input band (lanes
    padded to 128, sublanes to 8), the fp32 normalized band scratch of
    the fused GN kernels, the double-buffered weight tile and the
    double-buffered fp32 output block (``out_factor`` output rows per
    band row: 2 for the upsampler's two row phases).  With the fused
    RMSNorm (``rms_cout`` output channels) the output block spans every
    channel and a residual block of its size is double-buffered too."""
    band = (rows + 2) * _pad(width + 2, 8) * _pad(cin, 128)
    out = (out_factor * rows * _pad(width, 8)
           * _pad(rms_cout or tc, 128) * 4)
    wts = taps * _pad(cin, 8) * _pad(tc, 128) * 4
    total = 2 * band * itemsize + 2 * wts + 2 * out
    if fused_gn:
        total += band * 4
    if rms_cout:
        total += 2 * out
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


#: Filter taps per output-channel tile in VMEM: F(2,3) along rows holds a
#: ``[4, 3, Cin, tc]`` transformed filter, the direct loop ``[3, 3, ...]``.
WINOGRAD_TAPS = 12

#: Banded convs traced on each path since the process started, counted
#: per call site by the kernel dispatch (:mod:`repro.kernels.ops`; the
#: engine reports the deltas of a decode's warm-up on its ``lb.warm_up``
#: span).
PATHS: Dict[str, int] = {"winograd_rows": 0, "direct": 0}

#: Banded convs traced with each fused epilogue (``act="silu"``; ``rms=``
#: with ``residual=``), counted and reported like :data:`PATHS`.
EPILOGUES: Dict[str, int] = {"silu": 0, "rms_residual": 0}


def plan(x_shape: Tuple[int, ...], cout: int, itemsize: int, rows: int,
         block_cout: int, fused_gn: bool,
         fused_rms: bool = False) -> Tuple[int, int, str]:
    """(band rows, Cout tile, path) of one banded conv of ``x_shape``
    ``[N, H, W, Cin]``: an even band takes F(2,3) along rows
    (``'winograd_rows'``), an odd one the nine-tap loop (``'direct'``)."""
    _, h, width, cin = x_shape
    tc = cout_tile(cout, block_cout)
    rows = band_rows(h, width, cin, itemsize, rows, tc=tc,
                     taps=WINOGRAD_TAPS, fused_gn=fused_gn,
                     rms_cout=cout if fused_rms else 0)
    return rows, tc, "winograd_rows" if rows % 2 == 0 else "direct"


def winograd_weights(w: jax.Array) -> jax.Array:
    """``[3, 3, Cin, Cout]`` filter -> the ``[4, 3, Cin, Cout]`` F(2,3)
    row-transformed filter ``(w0, (w0+w1+w2)/2, (w0-w1+w2)/2, w2)`` over
    ``dy``, in fp32 (int8 taps in their integer units: the per-channel
    dequant scale still applies to the accumulator)."""
    w = w.astype(jnp.float32)
    return jnp.stack([w[0], (w[0] + w[1] + w[2]) * 0.5,
                      (w[0] - w[1] + w[2]) * 0.5, w[2]])


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
                 has_scale: bool, quantize: bool, winograd: bool,
                 act: Optional[str], rms_eps: Optional[float]):
    it = iter(refs)
    x_ref = next(it)                                 # [1, rows+2, W+2, Cin]
    if fused_gn:
        mean_ref, mul_ref, gbias_ref = next(it), next(it), next(it)
    w_ref = next(it)                     # [4, 3, Cin, tc] or [3, 3, Cin, tc]
    s_ref = next(it) if has_scale else None          # [1, tc]
    b_ref = next(it)                                 # [1, tc]
    if rms_eps is not None:
        g_ref, gb_ref, res_ref = next(it), next(it), next(it)
    o_ref = next(it)             # [1, rows, W, tc] ([1, rows, W, Cout]: rms)

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

    def epilogue(acc):
        if scale is not None:
            # scale is per output channel, so one fp32 multiply of the
            # summed accumulator dequantizes every tap exactly
            acc = acc * scale
        y = acc + bias
        return y * jax.nn.sigmoid(y) if act == "silu" else y

    if rms_eps is None:
        def store(r, acc):
            y = epilogue(acc)
            o_ref[0, r] = (quantize_u8(y) if quantize
                           else y.astype(o_ref.dtype))
    else:
        cout = o_ref.shape[-1]
        tc = b_ref.shape[-1]
        nt = cout // tc
        lanes = [slice(t * tc, (t + 1) * tc) for t in range(nt)]
        tile = pl.program_id(1)

        def normalize(r, y):
            # the last tile: the row's earlier tiles are in the resident
            # output block, so the statistics see every channel
            ss = jnp.sum(y * y, axis=-1, keepdims=True)
            for t in range(nt - 1):
                p = o_ref[0, r, :, lanes[t]].astype(jnp.float32)
                ss = ss + jnp.sum(p * p, axis=-1, keepdims=True)
            inv = jax.lax.rsqrt(ss / cout + rms_eps)
            for t in range(nt):
                p = (y if t == nt - 1
                     else o_ref[0, r, :, lanes[t]].astype(jnp.float32))
                v = ((p * inv) * g_ref[:, lanes[t]].astype(jnp.float32)
                     + gb_ref[:, lanes[t]].astype(jnp.float32))
                o_ref[0, r, :, lanes[t]] = (
                    res_ref[0, r, :, lanes[t]].astype(jnp.float32)
                    + v).astype(o_ref.dtype)

        def store(r, acc):
            y = epilogue(acc)
            for t in range(nt - 1):
                @pl.when(tile == t)
                def _keep(t=t):
                    o_ref[0, r, :, lanes[t]] = y.astype(o_ref.dtype)
            pl.when(tile == nt - 1)(lambda: normalize(r, y))

    def conv_row(r, carry):
        acc = None
        for dy in range(3):
            for dx in range(3):
                d = tap_dot(load(r + dy, dx), w_ref[dy, dx])
                acc = d if acc is None else acc + d
        store(r, acc)
        return carry

    def conv_pair(p, carry):
        # F(2,3) along rows: output rows r, r+1 from band rows r..r+3
        r = 2 * p
        m = [None] * 4
        for dx in range(3):
            d0, d1, d2, d3 = (load(r + i, dx).astype(jnp.float32)
                              for i in range(4))
            for k, t in enumerate((d0 - d2, d1 + d2, d2 - d1, d1 - d3)):
                d = tap_dot(t, w_ref[k, dx])
                m[k] = d if m[k] is None else m[k] + d
        store(r, m[0] + m[1] + m[2])
        store(r + 1, m[1] - m[2] - m[3])
        return carry

    if winograd:
        jax.lax.fori_loop(0, rows // 2, conv_pair, 0)
    else:
        jax.lax.fori_loop(0, rows, conv_row, 0)


def banded_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array], *,
                rows: int, block_cout: int, interpret: bool,
                w_scale: Optional[jax.Array] = None,
                gn: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
                quantize: bool = False, act: Optional[str] = None,
                rms: Optional[Tuple[jax.Array, jax.Array]] = None,
                residual: Optional[jax.Array] = None,
                eps: float = 1e-5) -> jax.Array:
    """x [N, H, W, Cin], w [3, 3, Cin, Cout] -> [N, H, W, Cout] (SAME).

    ``gn = (mean_c, mul_c, bias_c)`` normalizes + SiLU-activates the band
    in VMEM first (per-image ``[N, 1, Cin]`` mean and ``inv_std * scale``
    from :func:`repro.kernels.gn_silu.gn_coefficients`, ``[Cin]`` bias);
    ``quantize`` emits uint8 display pixels instead of ``x.dtype``.
    After the bias (and the int8 scale ahead of it), ``act="silu"``
    applies SiLU, and ``rms = (scale, bias)`` ``[Cout]`` with ``residual``
    ``[N, H, W, Cout]`` writes ``residual + rms_norm(y)`` (``eps`` inside
    the root).  The path follows the band's height (:func:`plan`)."""
    n, h, width, cin = x.shape
    cout = w.shape[-1]
    if act not in (None, "silu"):
        raise ValueError(f"banded_conv: act must be None or 'silu', "
                         f"not {act!r}")
    if (rms is None) != (residual is None) or (rms is not None
                                               and quantize):
        raise ValueError("banded_conv: rms= needs residual= (and no "
                         "quantize)")
    if b is None:
        b = jnp.zeros((cout,), x.dtype)
    rms_eps = None if rms is None else float(eps)
    rows, tc, path = plan(x.shape, cout, x.dtype.itemsize, rows, block_cout,
                          gn is not None, rms is not None)
    winograd = path == "winograd_rows"
    if winograd:
        w = winograd_weights(w)                      # [4, 3, Cin, Cout]
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
    in_specs.append(pl.BlockSpec((w.shape[0], 3, cin, tc),
                                 lambda i, c: (0, 0, 0, c)))
    operands.append(w)
    if w_scale is not None:
        in_specs.append(pl.BlockSpec((1, tc), lambda i, c: (0, c)))
        operands.append(w_scale.reshape(1, cout))
    in_specs.append(pl.BlockSpec((1, tc), lambda i, c: (0, c)))
    operands.append(b.reshape(1, cout))
    if rms is None:
        out_spec = pl.BlockSpec((1, rows, width, tc),
                                lambda i, c: (i, 0, 0, c))
    else:
        # every channel of the band's rows, resident across the Cout tiles
        out_spec = pl.BlockSpec((1, rows, width, cout),
                                lambda i, c: (i, 0, 0, 0))
        in_specs += [pl.BlockSpec((1, cout), lambda i, c: (0, 0)),
                     pl.BlockSpec((1, cout), lambda i, c: (0, 0)),
                     pl.BlockSpec((1, rows, width, cout),
                                  lambda i, c: (i, 0, 0, 0))]
        # the residual in the output's band shape: given as [N, H, W, C],
        # its layout constraint made XLA copy the next conv's staged bands
        # twice at batch > 1 (3.5 GiB more temporaries at DC-AE bucket 4)
        operands += [rms[0].reshape(1, cout), rms[1].reshape(1, cout),
                     residual.reshape(n * nb, rows, width, cout)]

    out = pl.pallas_call(
        functools.partial(_conv_kernel, rows=rows, width=width, nb=nb,
                          fused_gn=gn is not None,
                          has_scale=w_scale is not None, quantize=quantize,
                          winograd=winograd, act=act, rms_eps=rms_eps),
        grid=(n * nb, cout // tc),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n * nb, rows, width, cout),
            jnp.uint8 if quantize else x.dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*operands)
    return out.reshape(n, h, width, cout)


@functools.partial(jax.jit, static_argnames=("rows", "block_cout",
                                             "interpret", "act", "eps"))
def conv3x3(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
            rows: int = 32, block_cout: int = 128,
            interpret: bool = False,
            w_scale: Optional[jax.Array] = None, act: Optional[str] = None,
            rms: Optional[Tuple[jax.Array, jax.Array]] = None,
            residual: Optional[jax.Array] = None,
            eps: float = 1e-5) -> jax.Array:
    """x [N, H, W, Cin], w [3, 3, Cin, Cout] -> [N, H, W, Cout] (SAME).

    ``w`` may be stored float32/bfloat16 or int8 with ``w_scale`` [Cout]
    — the per-channel dequant then happens on the accumulator in VMEM.  An
    even band takes the fp32 row-transformed filter
    (:func:`winograd_weights`, int8 taps in integer units); an odd band
    casts each tap tile to fp32 in VMEM.  ``act``, ``rms``, ``residual``
    and ``eps`` select the fused epilogues of :func:`banded_conv`."""
    return banded_conv(x, w, b, rows=rows, block_cout=block_cout,
                       interpret=interpret, w_scale=w_scale, act=act,
                       rms=rms, residual=residual, eps=eps)
