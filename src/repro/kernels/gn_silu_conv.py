"""Fused GroupNorm + SiLU + 3x3 conv — the decoder's res-block hot path.

Every res block in the VAE decoder is ``conv3x3(silu(gn(x)))``; unfused,
the normalized activation makes a full HBM round-trip between the GN+SiLU
kernel and the conv.  This kernel keeps it in VMEM: the input row band
(with 1-row halo) is normalized, activated, and immediately consumed by
the nine implicit-GEMM filter-tap matmuls, eliminating one read + one
write of the [H, W, C] activation per block — the decoder's dominant
memory term (see the roofline in :mod:`repro.vae.serve` and the traffic
rows in ``benchmarks/bench_kernels.py``).

Structure (GN stats must exist before the conv can run):
  pass 1  per-channel partial sums -> per-image GN coefficients
          (:func:`repro.kernels.gn_silu.gn_coefficients`, shared with
          :mod:`repro.kernels.gn_silu`);
  pass 2  grid (N*nb, Cout/tc): per row-band, normalize + SiLU the band
          into a VMEM scratch — including its halo rows, which are real
          neighbor pixels, and re-zeroing the conv's SAME padding ring
          (``silu(gn(0)) != 0``) — then accumulate the nine shifted
          (W, Cin) x (Cin, tc) MXU matmuls per output row exactly as
          :mod:`repro.kernels.conv3x3` does (:func:`banded_conv`).
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
def gn_silu_conv3x3(x: jax.Array, scale: jax.Array, bias: jax.Array,
                    w: jax.Array, b: Optional[jax.Array] = None,
                    groups: int = 32, eps: float = 1e-6, rows: int = 32,
                    block_cout: int = 128, stats_tile: int = 1024,
                    interpret: bool = False,
                    w_scale: Optional[jax.Array] = None) -> jax.Array:
    """``conv3x3(silu(group_norm(x)))`` fused.  x [N, H, W, Cin] NHWC,
    scale/bias [Cin], w [3, 3, Cin, Cout], b [Cout] -> [N, H, W, Cout]."""
    mean_c, mul_c = gn_coefficients(x, scale, groups, eps, stats_tile,
                                     interpret)
    return banded_conv(x, w, b, rows=rows, block_cout=block_cout,
                       interpret=interpret, w_scale=w_scale,
                       gn=(mean_c, mul_c, bias.astype(jnp.float32)))
