"""ReLU linear attention (EfficientViT / DC-AE) in one pass over the tokens.

Per head, with ``q, k, v`` of ``d`` channels over ``T`` tokens:

    S   = sum_t v_t relu(k_t)^T          (d x d)
    z   = sum_t relu(k_t)                (d)
    o_t = (S relu(q_t)) / (z . relu(q_t) + eps)

which is diffusers' ``([v; 1] k^T) q`` followed by the division of the
first ``d`` rows by the last, in fp32.

Layout.  Each *source* is one packed ``[N, T, 3 * Cq]`` array, part-major:
every head's ``q`` in ``[0, Cq)``, its ``k`` in ``[Cq, 2 Cq)`` and its
``v`` in ``[2 Cq, 3 Cq)``, ``d`` contiguous channels per head in each
part.  The output is ``[N, T, S * Cq]``: source ``s``'s heads in
``[s * Cq, (s + 1) * Cq)``.  A DC-AE block has two sources (the
projected ``q/k/v`` and their multi-scale aggregate), so one call covers
all of a block's heads and writes the concatenation that ``to_out``
reads.

Grid ``(N, source, lane block, phase, token tile)``.  A lane block holds
``128 / d`` heads.  Phase 0 streams the ``k`` and ``v`` tiles and
accumulates ``S`` (one ``(128, 128)`` MXU product per tile, masked to
its per-head diagonal blocks at the end) and ``z`` in VMEM; phase 1
streams the ``q`` tiles, applies both and writes the output tile.  The
index maps hold each operand's block where its phase does not read it,
so every tile of ``q``, ``k`` and ``v`` is read once and every output
tile written once.  Tokens past ``T`` in a last partial tile are masked
out of the sums.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv3x3 import HIGHEST, compiler_params

#: Kernel calls traced since the process started, counted per call site by
#: the kernel dispatch (:mod:`repro.kernels.ops`; the engine reports a
#: decode warm-up's delta on its ``lb.warm_up`` span).
TRACED: Dict[str, int] = {"linear_attention": 0}

LANES = 128


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(*refs, n_src: int, tokens: int, tile: int, head_dim: int,
            eps: float):
    ins = refs[:3 * n_src]
    o_ref, s_acc, z_acc = refs[3 * n_src:]
    src, phase, t = pl.program_id(1), pl.program_id(3), pl.program_id(4)
    lb = o_ref.shape[-1]
    row = t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, lb), 0)
    valid = row < tokens
    same_head = (jax.lax.broadcasted_iota(jnp.int32, (lb, lb), 0) // head_dim
                 == jax.lax.broadcasted_iota(jnp.int32, (lb, lb), 1)
                 // head_dim)

    def attend(q_ref, k_ref, v_ref):
        @pl.when((phase == 0) & (t == 0))
        def _reset():
            s_acc[...] = jnp.zeros_like(s_acc)
            z_acc[...] = jnp.zeros_like(z_acc)

        @pl.when(phase == 0)
        def _accumulate():
            k = jnp.where(valid, jnp.maximum(k_ref[0].astype(jnp.float32),
                                             0.0), 0.0)
            v = jnp.where(valid, v_ref[0].astype(jnp.float32), 0.0)
            # S[i, j] = sum_t v[t, i] k[t, j]
            s_acc[...] += _dot(v, k, ((0,), (0,)))
            z_acc[...] += jnp.sum(k, axis=0, keepdims=True)

        @pl.when(phase == 1)
        def _apply():
            q = jnp.maximum(q_ref[0].astype(jnp.float32), 0.0)
            kv = jnp.where(same_head, s_acc[...], 0.0)
            kz = jnp.where(same_head, z_acc[...], 0.0)   # [i, j] = z[j]
            num = _dot(q, kv, ((1,), (1,)))
            den = _dot(q, kz, ((1,), (1,)))
            o_ref[0] = (num / (den + eps)).astype(o_ref.dtype)

    for s in range(n_src):
        pl.when(src == s)(functools.partial(attend, *ins[3 * s:3 * s + 3]))


def _operand_map(s: int, part: int, n_tiles: int, n_lanes: int,
                 lane0: int):
    """Block index of source ``s``'s ``part`` (0 q, 1 k, 2 v): streamed in
    its phase, held at its first block before its source's turn and at its
    last after it, and at the last streamed tile in the other phase."""
    def index(n, src, c, phase, t):
        if part == 0:
            tok = jnp.where(phase == 1, t, 0)
        else:
            tok = jnp.where(phase == 0, t, n_tiles - 1)
        tok = jnp.where(src < s, 0, jnp.where(src > s, n_tiles - 1, tok))
        lane = jnp.where(src < s, 0, jnp.where(src > s, n_lanes - 1, c))
        return n, tok, lane0 + lane
    return index


@functools.partial(jax.jit, static_argnames=("head_dim", "eps",
                                             "block_tokens", "interpret"))
def linear_attention(sources: Sequence[jax.Array], head_dim: int,
                     eps: float = 1e-15, block_tokens: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """``sources``: packed part-major ``[N, T, 3 * Cq]`` arrays (module
    docstring) -> ``[N, T, len(sources) * Cq]``."""
    sources = tuple(sources)
    n, tokens, c3 = sources[0].shape
    cq = c3 // 3
    if c3 % 3 or cq % head_dim or any(s.shape != sources[0].shape
                                      for s in sources):
        raise ValueError(f"linear_attention: sources must share a shape "
                         f"[N, T, 3 * Cq] with Cq a multiple of "
                         f"head_dim={head_dim}: "
                         f"{[s.shape for s in sources]}")
    tile = tokens if tokens <= block_tokens else block_tokens
    n_tiles = pl.cdiv(tokens, tile)
    aligned = cq % LANES == 0 and LANES % head_dim == 0
    lb = LANES if aligned else cq
    n_lanes = cq // lb

    in_specs, operands = [], []
    for s, x in enumerate(sources):
        for part in range(3):
            if aligned:        # one packed array, three lane offsets
                operand, lane0 = x, part * n_lanes
            else:              # small widths: the part as its own array
                operand, lane0 = x[..., part * cq:(part + 1) * cq], 0
            in_specs.append(pl.BlockSpec(
                (1, tile, lb),
                _operand_map(s, part, n_tiles, n_lanes, lane0)))
            operands.append(operand)

    def out_map(b, src, c, phase, t):
        return b, jnp.where(phase == 1, t, 0), src * n_lanes + c

    return pl.pallas_call(
        functools.partial(_kernel, n_src=len(sources), tokens=tokens,
                          tile=tile, head_dim=head_dim, eps=eps),
        grid=(n, len(sources), n_lanes, 2, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile, lb), out_map),
        out_shape=jax.ShapeDtypeStruct((n, tokens, len(sources) * cq),
                                       sources[0].dtype),
        scratch_shapes=[pltpu.VMEM((lb, lb), jnp.float32),
                        pltpu.VMEM((1, lb), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*operands)

