"""VAE building blocks in pure JAX (NHWC layout — channels on the TPU lane
axis).  Hot spots route through :mod:`repro.kernels.ops` so the Pallas TPU
kernels and the XLA reference path are interchangeable (``impl=`` flag).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def fp32_math(fn):
    """Trace ``fn`` with fp32 matmul/conv precision.  On TPU the default
    precision of an fp32 dot is one bf16 pass; the decode's XLA ops (1x1
    shortcut convs, attention projections) would then drift from the fp32
    oracle by more than the ±1-LSB contract.  A no-op on CPU."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def conv_init(key, kh: int, kw: int, cin: int, cout: int,
              dtype=jnp.float32) -> Params:
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout), dtype) / math.sqrt(fan_in)
    return {"w": w, "b": jnp.zeros((cout,), dtype)}


def gn_init(channels: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((channels,), dtype),
            "bias": jnp.zeros((channels,), dtype)}


def dense_init(key, cin: int, cout: int, dtype=jnp.float32) -> Params:
    w = jax.random.normal(key, (cin, cout), dtype) / math.sqrt(cin)
    return {"w": w, "b": jnp.zeros((cout,), dtype)}


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def conv2d(x: jax.Array, p: Params, stride: int = 1,
           padding: str | Tuple = "SAME",
           impl: Optional[str] = None) -> jax.Array:
    """NHWC conv; channels-last keeps C on the 128-wide lane dimension.

    Unstrided SAME 3x3 convs — every conv on the decode path except the
    1x1 shortcut and the strided downsample — dispatch through
    :func:`repro.kernels.ops.conv3x3` so the Pallas implicit-GEMM kernel
    is live on the read path (the XLA impl is the identical lax conv).
    """
    from repro.kernels import ops                     # late import (no cycle)
    w = p["w"]
    if w.shape[:2] == (3, 3) and stride == 1 and padding == "SAME":
        return ops.conv3x3(x, w, p["b"], impl=impl)
    if isinstance(w, ops.QuantizedWeight):
        # non-3x3 convs (the 1x1 shortcut) have no Pallas path: dequant
        # transiently for the lax conv (tiny weights, folded by XLA)
        w = w.dequant(x.dtype)
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(stride, stride),
        padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"].astype(x.dtype)


def group_norm(x: jax.Array, p: Params, groups: int = 32,
               eps: float = 1e-6) -> jax.Array:
    """GroupNorm over (H, W, C/g) with fp32 statistics."""
    n, h, w, c = x.shape
    xf = x.astype(jnp.float32).reshape(n, h * w, groups, c // groups)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = xf.var(axis=(1, 3), keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(n, h, w, c)
    return (xf * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def gn_silu(x: jax.Array, p: Params, groups: int = 32,
            impl: Optional[str] = None) -> jax.Array:
    """Fused GroupNorm + SiLU — the decoder's memory-bound hot spot."""
    from repro.kernels import ops                     # late import (no cycle)
    return ops.group_norm_silu(x, p["scale"], p["bias"], groups=groups,
                               impl=impl)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def resnet_block_init(key, cin: int, cout: int, dtype=jnp.float32) -> Params:
    k = jax.random.split(key, 3)
    p = {
        "norm1": gn_init(cin, dtype),
        "conv1": conv_init(k[0], 3, 3, cin, cout, dtype),
        "norm2": gn_init(cout, dtype),
        "conv2": conv_init(k[1], 3, 3, cout, cout, dtype),
    }
    if cin != cout:
        p["shortcut"] = conv_init(k[2], 1, 1, cin, cout, dtype)
    return p


def resnet_block(x: jax.Array, p: Params, groups: int = 32,
                 impl: Optional[str] = None) -> jax.Array:
    """GN+SiLU+conv3x3 twice plus shortcut, via the fused kernel.

    Each GN+SiLU+conv triple dispatches through
    :func:`repro.kernels.ops.gn_silu_conv3x3`, keeping the normalized
    activation in VMEM instead of round-tripping it through HBM between
    the norm and the conv (the XLA impl composes the two oracles and is
    bit-identical to the unfused path).  The 1x1 shortcut stays on XLA.
    """
    from repro.kernels import ops                     # late import (no cycle)
    h = ops.gn_silu_conv3x3(x, p["norm1"]["scale"], p["norm1"]["bias"],
                            p["conv1"]["w"], p["conv1"]["b"],
                            groups=groups, impl=impl)
    h = ops.gn_silu_conv3x3(h, p["norm2"]["scale"], p["norm2"]["bias"],
                            p["conv2"]["w"], p["conv2"]["b"],
                            groups=groups, impl=impl)
    if "shortcut" in p:
        x = conv2d(x, p["shortcut"], impl=impl)
    return x + h


def attn_block_init(key, c: int, dtype=jnp.float32) -> Params:
    k = jax.random.split(key, 4)
    return {
        "norm": gn_init(c, dtype),
        "q": dense_init(k[0], c, c, dtype),
        "k": dense_init(k[1], c, c, dtype),
        "v": dense_init(k[2], c, c, dtype),
        "proj": dense_init(k[3], c, c, dtype),
    }


def attn_block(x: jax.Array, p: Params, groups: int = 32,
               impl: Optional[str] = None) -> jax.Array:
    """Single-head self-attention over the H*W token grid (mid-block)."""
    from repro.kernels import ops
    n, h, w, c = x.shape
    y = group_norm(x, p["norm"], groups=groups)
    y = y.reshape(n, h * w, c)
    q = y @ p["q"]["w"].astype(y.dtype) + p["q"]["b"].astype(y.dtype)
    k = y @ p["k"]["w"].astype(y.dtype) + p["k"]["b"].astype(y.dtype)
    v = y @ p["v"]["w"].astype(y.dtype) + p["v"]["b"].astype(y.dtype)
    # [n, hw, c] -> [n, 1 head, hw, c]
    o = ops.flash_attention(q[:, None], k[:, None], v[:, None],
                            causal=False, impl=impl)[:, 0]
    o = o @ p["proj"]["w"].astype(o.dtype) + p["proj"]["b"].astype(o.dtype)
    return x + o.reshape(n, h, w, c)


def upsample_init(key, c: int, dtype=jnp.float32) -> Params:
    return {"conv": conv_init(key, 3, 3, c, c, dtype)}


def upsample(x: jax.Array, p: Params,
             impl: Optional[str] = None) -> jax.Array:
    """Nearest-neighbor 2x + 3x3 conv (SD decoder upsampler).

    Dispatches through :func:`repro.kernels.ops.upsample_conv3x3`: the
    Pallas kernel computes the conv directly from the pre-upsample tensor
    (phase-decomposed 2x2 taps — the 4x upsampled intermediate never
    touches HBM); the XLA impl is the identical repeat + conv.
    """
    from repro.kernels import ops                     # late import (no cycle)
    return ops.upsample_conv3x3(x, p["conv"]["w"], p["conv"]["b"], impl=impl)


def downsample_init(key, c: int, dtype=jnp.float32) -> Params:
    return {"conv": conv_init(key, 3, 3, c, c, dtype)}


def downsample(x: jax.Array, p: Params) -> jax.Array:
    """Strided 3x3 conv with SD's asymmetric (0,1) padding."""
    x = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
    y = jax.lax.conv_general_dilated(
        x, p["conv"]["w"].astype(x.dtype), window_strides=(2, 2),
        padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["conv"]["b"].astype(x.dtype)
