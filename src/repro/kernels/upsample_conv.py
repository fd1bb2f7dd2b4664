"""Fused nearest-2x upsample + 3x3 SAME conv — the decoder's upsampler.

Every decoder level but the last ends in ``conv3x3(nearest_upsample_2x(x))``.
Unfused, XLA materializes the upsampled ``[N, 2H, 2W, C]`` intermediate in
HBM (a 4x-sized tensor written once and read once before every upsampler
conv) — the single largest avoidable traffic term on the decode path after
the res-block fusion.  This kernel computes the conv *directly from the
pre-upsample tensor*, so the 4x intermediate never exists in HBM.

The trick is a phase decomposition of the composite op.  An output pixel
``(2i+pi, 2j+pj)`` (phases ``pi, pj in {0, 1}``) reads a 3x3 window of the
upsampled image, but nearest upsampling makes those nine taps hit only a
2x2 neighborhood of ``x`` — with known multiplicities.  Collapsing the
duplicated taps *into the weights* (done once in the wrapper, not per
pixel) turns each phase into an independent 2x2 conv on ``x``:

  phase rows  pi=0: x[i-1]*w[0]     + x[i]*(w[1]+w[2])
              pi=1: x[i]*(w[0]+w[1]) + x[i+1]*w[2]        (cols identical)

so the fused op is 4 phases x 4 taps = 16 MXU matmuls over ``rows*W``
pixels vs 9 matmuls over ``4*rows*W`` for conv-on-upsampled: **2.25x fewer
MACs** on top of the traffic win.  A grid step computes one column phase
``pj`` (both row phases); the phases land in an output layout whose
memory order is already the interleaved ``[2H, 2W]`` image.

Grid/banding follows :mod:`repro.kernels.conv3x3`: the wrapper stages
halo-padded input row bands once in HBM; zero halos at image edges are
exactly the SAME padding of the upsampled image, so no ring masking is
needed (the input is pre-activation — zeros stay zeros).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.conv3x3 import (band_rows, compiler_params, cout_tile,
                                   materialize_bands, tap_dot)

#: tap groups per phase: phase p sums these dy (dx) taps into its 2 row
#: (col) offsets — offset index a lands on band row ``p + a``
_PHASE_TAPS = {0: ((0,), (1, 2)), 1: ((0, 1), (2,))}


def phase_weights(w: jax.Array) -> jax.Array:
    """Collapse a ``[3, 3, Cin, Cout]`` filter into the ``[2, 2, 2, 2,
    Cin, Cout]`` per-phase 2x2 filters (index order ``[pi, pj, a, b]``)."""
    rows = []
    for pi in (0, 1):
        cols = []
        for pj in (0, 1):
            taps_a = []
            for dys in _PHASE_TAPS[pi]:
                taps_b = []
                for dxs in _PHASE_TAPS[pj]:
                    tap = sum(w[dy, dx] for dy in dys for dx in dxs)
                    taps_b.append(tap)
                taps_a.append(jnp.stack(taps_b))
            cols.append(jnp.stack(taps_a))
        rows.append(jnp.stack(cols))
    return jnp.stack(rows)


def _upsample_conv_kernel(x_ref, w_ref, *refs, rows: int, width: int):
    # refs is (b_ref, o_ref), or (s_ref, b_ref, o_ref) with a per-output-
    # channel dequant scale (int8 weight storage)
    s_ref, b_ref, o_ref = refs if len(refs) == 3 else (None, *refs)
    bias = b_ref[...].astype(jnp.float32)            # [1, tc]
    w_scale = None if s_ref is None else s_ref[...].astype(jnp.float32)

    def column_phase(pj: int):
        # one column phase per grid step: output rows 2i+pi, columns
        # 2j+pj of the band; w_ref is [pi, a, b, Cin, tc] for this pj
        def out_row(r, carry):
            for pi in range(2):
                acc = None
                for a in range(2):
                    for b in range(2):
                        d = tap_dot(x_ref[0, r + pi + a,
                                          pl.ds(pj + b, width), :],
                                    w_ref[pi, a, b])
                        acc = d if acc is None else acc + d
                if w_scale is not None:
                    acc = acc * w_scale
                o_ref[0, r, pi] = (acc + bias).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, rows, out_row, 0)

    pj = pl.program_id(1)
    for phase in (0, 1):
        pl.when(pj == phase)(functools.partial(column_phase, phase))


@functools.partial(jax.jit, static_argnames=("rows", "block_cout",
                                             "interpret"))
def upsample_conv3x3(x: jax.Array, w: jax.Array,
                     b: Optional[jax.Array] = None, rows: int = 16,
                     block_cout: int = 128,
                     interpret: bool = False,
                     w_scale: Optional[jax.Array] = None) -> jax.Array:
    """``conv3x3(nearest_upsample_2x(x))`` fused.  x [N, H, W, Cin] NHWC,
    w [3, 3, Cin, Cout], b [Cout] -> [N, 2H, 2W, Cout] (SAME).

    int8 ``w`` (with ``w_scale`` [Cout]) is phase-collapsed in int16 — a
    collapsed tap sums at most 4 int8 values, which int16 holds exactly,
    and the shared per-channel scale distributes over the sum — then
    dequantized on the phase accumulators in VMEM.

    The kernel writes ``[N*nb, rows, 2 (pi), W, 2 (pj) * Cout]``, which
    is the ``[N, 2H, 2W, Cout]`` output's own memory order, so the phase
    interleave costs a free reshape instead of an in-VMEM shuffle."""
    n, h, width, cin = x.shape
    cout = w.shape[-1]
    if b is None:
        b = jnp.zeros((cout,), x.dtype)

    tc = cout_tile(cout, block_cout)
    nc = cout // tc
    rows = band_rows(h, width, cin, x.dtype.itemsize, rows, tc=tc, taps=8,
                     out_factor=2)
    nb = h // rows
    if w.dtype == jnp.int8:
        wc = phase_weights(w.astype(jnp.int16))      # exact: |sum| <= 4*127
    else:
        wc = phase_weights(w)                        # [2, 2, 2, 2, Cin, Cout]

    in_specs = [
        pl.BlockSpec((1, rows + 2, width + 2, cin),
                     lambda i, p, c: (i, 0, 0, 0)),
        pl.BlockSpec((2, None, 2, 2, cin, tc),
                     lambda i, p, c: (0, p, 0, 0, 0, c)),
    ]
    operands = [materialize_bands(x, rows), wc]
    if w_scale is not None:
        in_specs.append(pl.BlockSpec((1, tc), lambda i, p, c: (0, c)))
        operands.append(w_scale.reshape(1, cout))
    in_specs.append(pl.BlockSpec((1, tc), lambda i, p, c: (0, c)))
    operands.append(b.reshape(1, cout))

    out = pl.pallas_call(
        functools.partial(_upsample_conv_kernel, rows=rows, width=width),
        grid=(n * nb, 2, nc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, 2, width, tc),
                               lambda i, p, c: (i, 0, 0, 0, p * nc + c)),
        out_shape=jax.ShapeDtypeStruct((n * nb, rows, 2, width, 2 * cout),
                                       x.dtype),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*operands)
    return out.reshape(n, 2 * h, 2 * width, cout)
