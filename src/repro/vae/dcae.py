"""DC-AE decoder (Deep Compression Autoencoder, the VAE of SANA 1.0): the
second decoder family of the latent-first store.

f32c32 stores a 1024² image as a 32x32x32 latent, 1/32 resolution, 8x
fewer durable bytes than SD3.5's 128x128x16.  The decode (diffusers
``AutoencoderDC`` ``Decoder.forward``, channels-last here):

    z / scaling_factor -> conv_in 3x3 + repeat_interleave(z) (channels)
      -> stages, deepest first: an up block (all but the deepest), then
         ResBlocks or EfficientViT blocks
      -> RMSNorm -> ReLU -> conv_out 3x3 -> image in [-1, 1]

* ResBlock: ``x + RMSNorm(conv2(SiLU(conv1(x))))``; conv2 has no bias.
* Up block (``DCUpBlock2d``, interpolate, with shortcut): ``conv3x3(
  nearest_2x(x)) + pixel_shuffle(repeat_interleave(x, r), 2)`` with
  ``r = 4 Cout / Cin``.
* EfficientViT block: multi-scale ReLU linear attention with a residual,
  then ``GLUMBConv`` with a residual.  The attention projects ``q|k|v``
  (three 1x1, no bias), aggregates them (depthwise kxk, then grouped 1x1
  of ``head_dim`` channels a group, no bias), regroups the ``6C``
  channels as heads of ``3 head_dim`` *consecutive* channels (q, k, v of
  ``head_dim`` each), attends, projects ``2C -> C`` and RMS-normalizes.
  GLUMBConv: 1x1 to 8C (bias), SiLU, depthwise 3x3 (bias), ``a *
  SiLU(g)``, 1x1 4C -> C (no bias), RMSNorm.
* RMSNorm: over channels, ``x * rsqrt(mean(x^2) + 1e-5) * scale + bias``
  with fp32 statistics.

Kernels: every 3x3 conv runs the banded conv (:func:`ops.conv3x3`), the
up-block convs the fused upsampler (:func:`ops.upsample_conv3x3`), the
attention core :func:`ops.linear_attention`, and the output path
:func:`ops.rms_output_epilogue`; the 1x1 projections, the depthwise and
grouped convs, the norms and the shortcuts are XLA.  The linear
attention reads each source packed part-major (every head's q, then k,
then v): the ``q|k|v`` projection columns and the aggregation's channels
are permuted into that order at trace time (:func:`part_major`), which
the per-channel depthwise and the 32-aligned grouped convs commute with.
Each stage runs under a ``jax.named_scope`` (``dcae.s<i>.up``,
``dcae.s<i>.res``, ``dcae.s<i>.evit``), so a profiler's view splits the
decode by stage.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.vae.layers import fp32_math

RES, EVIT = "ResBlock", "EfficientViTBlock"
RMS_EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DCAEConfig:
    """A DC-AE decoder's published settings (diffusers ``AutoencoderDC``
    ``decoder_*`` keys; one norm and one activation for every stage)."""
    name: str = "dcae_f32c32"
    latent_channels: int = 32
    block_out_channels: Tuple[int, ...] = (128, 256, 512, 512, 1024, 1024)
    block_types: Tuple[str, ...] = (RES,) * 3 + (EVIT,) * 3
    layers_per_block: Tuple[int, ...] = (3,) * 6
    qkv_multiscales: Tuple[Tuple[int, ...], ...] = ((), (), (), (5,), (5,),
                                                    (5,))
    attention_head_dim: int = 32
    norm_type: str = "rms_norm"
    act_fn: str = "silu"
    upsample_block_type: str = "interpolate"
    scaling_factor: float = 0.41407
    image_channels: int = 3
    dtype: Any = jnp.float32

    def __post_init__(self):
        n = len(self.block_out_channels)
        if not (len(self.block_types) == len(self.layers_per_block)
                == len(self.qkv_multiscales) == n):
            raise ValueError(f"{self.name}: block types, layers and qkv "
                             f"multiscales need one entry per stage ({n})")
        if (self.norm_type, self.act_fn, self.upsample_block_type) != (
                "rms_norm", "silu", "interpolate"):
            raise ValueError(
                f"{self.name}: the program's DC-AE decoder runs rms_norm, "
                f"silu and interpolate up blocks only, not "
                f"{(self.norm_type, self.act_fn, self.upsample_block_type)}")
        if min(self.layers_per_block) < 1:
            raise ValueError(f"{self.name}: every stage needs a block")
        for kind, c in zip(self.block_types, self.block_out_channels):
            if kind not in (RES, EVIT):
                raise ValueError(f"{self.name}: unknown block type {kind!r}")
            if kind == EVIT and c % self.attention_head_dim:
                raise ValueError(f"{self.name}: {c} channels do not split "
                                 f"into heads of {self.attention_head_dim}")

    @property
    def spatial_factor(self) -> int:
        return 2 ** (len(self.block_out_channels) - 1)

    def latent_shape(self, image_hw: int) -> Tuple[int, int, int]:
        s = image_hw // self.spatial_factor
        return (s, s, self.latent_channels)


DCAE_F32C32 = DCAEConfig()


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_decoder(key, cfg: DCAEConfig) -> Dict[str, Any]:
    """Seeded random decoder tree: N(0, 1/fan_in) weights, zero biases,
    unit RMSNorm scales.  Stages are listed in decode order (deepest
    first); each holds its ``upsample`` (all but the first) and ``blocks``.
    """
    keys = iter(jax.random.split(key, 4096))
    dt = cfg.dtype

    def w(shape, fan_in):
        return jax.random.normal(next(keys), shape, dt) / np.sqrt(fan_in)

    def conv(k, cin, cout, bias=True, groups=1):
        p = {"w": w((k, k, cin // groups, cout), k * k * cin // groups)}
        if bias:
            p["b"] = jnp.zeros((cout,), dt)
        return p

    def dense(cin, cout, bias=False):
        p = {"w": w((cin, cout), cin)}
        if bias:
            p["b"] = jnp.zeros((cout,), dt)
        return p

    def norm(c):
        return {"scale": jnp.ones((c,), dt), "bias": jnp.zeros((c,), dt)}

    def block(kind, c, scales):
        if kind == RES:
            return {"conv1": conv(3, c, c), "conv2": conv(3, c, c, False),
                    "norm": norm(c)}
        d = cfg.attention_head_dim
        return {"attn": {
                    "to_q": dense(c, c), "to_k": dense(c, c),
                    "to_v": dense(c, c),
                    "multiscale": [
                        {"proj_in": conv(k, 3 * c, 3 * c, False, 3 * c),
                         "proj_out": conv(1, 3 * c, 3 * c, False,
                                          3 * c // d)}
                        for k in scales],
                    "to_out": dense((1 + len(scales)) * c, c),
                    "norm": norm(c)},
                "mlp": {"conv_inverted": dense(c, 8 * c, True),
                        "conv_depth": conv(3, 8 * c, 8 * c, True, 8 * c),
                        "conv_point": dense(4 * c, c),
                        "norm": norm(c)}}

    chs = cfg.block_out_channels
    params: Dict[str, Any] = {
        "conv_in": conv(3, cfg.latent_channels, chs[-1]), "up": []}
    for i in reversed(range(len(chs))):
        stage: Dict[str, Any] = {}
        if i < len(chs) - 1:
            stage["upsample"] = conv(3, chs[i + 1], chs[i])
        stage["blocks"] = [block(cfg.block_types[i], chs[i],
                                 cfg.qkv_multiscales[i])
                           for _ in range(cfg.layers_per_block[i])]
        params["up"].append(stage)
    params["norm_out"] = norm(chs[0])
    params["conv_out"] = conv(3, chs[0], cfg.image_channels)
    return params


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _f32(w, dtype=jnp.float32):
    """A weight as ``dtype``; int8 storage is dequantized (the XLA paths
    have no per-tile dequant)."""
    from repro.kernels.ops import QuantizedWeight
    if isinstance(w, QuantizedWeight):
        return w.dequant(dtype)
    return w.astype(dtype)


def rms_norm(x: jax.Array, p) -> jax.Array:
    from repro.kernels.ref import rms_norm_ref
    return rms_norm_ref(x, p["scale"], p["bias"], RMS_EPS)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def _dense(x: jax.Array, p) -> jax.Array:
    y = jnp.einsum("...c,cd->...d", x, _f32(p["w"], x.dtype),
                   precision=HIGHEST)
    return y + p["b"].astype(x.dtype) if "b" in p else y


def _grouped_conv(x: jax.Array, w, groups: int, b=None) -> jax.Array:
    y = jax.lax.conv_general_dilated(
        x, _f32(w, x.dtype), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST)
    return y if b is None else y + b.astype(x.dtype)


def channel_shortcut(x: jax.Array, cout: int) -> jax.Array:
    """``pixel_shuffle(repeat_interleave(x, r), 2)`` with ``r = 4 Cout /
    Cin``: ``out[2h+i, 2w+j, c] = x[h, w, (4c + 2i + j) // r]``."""
    n, h, w, cin = x.shape
    y = jnp.repeat(x, 4 * cout // cin, axis=-1)           # [n, h, w, 4 cout]
    y = y.reshape(n, h, w, cout, 2, 2).transpose(0, 1, 4, 2, 5, 3)
    return y.reshape(n, 2 * h, 2 * w, cout)


def part_major(channels: int, head_dim: int) -> np.ndarray:
    """Channel order of a packed linear-attention source: position ``p``
    of the part-major layout (every head's q, then k, then v) holds
    channel ``perm[p]`` of diffusers' layout, where head ``h`` owns the
    ``3 head_dim`` consecutive channels ``[q | k | v]``."""
    heads = channels // (3 * head_dim)
    return np.arange(channels).reshape(heads, 3, head_dim).transpose(
        1, 0, 2).reshape(-1)


def res_block(x: jax.Array, p, impl: Optional[str] = None) -> jax.Array:
    from repro.kernels import ops                     # late import (no cycle)
    h = ops.conv3x3(x, p["conv1"]["w"], p["conv1"]["b"], impl=impl)
    h = ops.conv3x3(silu(h), p["conv2"]["w"], None, impl=impl)
    return x + rms_norm(h, p["norm"])


def linear_attention_block(x: jax.Array, p, head_dim: int,
                           impl: Optional[str] = None) -> jax.Array:
    """Multi-scale ReLU linear attention + RMSNorm + residual."""
    from repro.kernels import ops
    n, h, w, c = x.shape
    if h * w <= head_dim:
        raise ValueError(f"DC-AE attention at {h}x{w} would be quadratic "
                         f"(H*W <= {head_dim}); only the linear form is "
                         f"implemented")
    perm = part_major(3 * c, head_dim)
    wqkv = jnp.concatenate([_f32(p[k]["w"], x.dtype)
                            for k in ("to_q", "to_k", "to_v")], axis=1)
    qkv = jnp.einsum("nhwc,cd->nhwd", x, wqkv[:, perm], precision=HIGHEST)
    sources = [qkv]
    for ms in p["multiscale"]:
        y = _grouped_conv(qkv, _f32(ms["proj_in"]["w"])[..., perm], 3 * c)
        sources.append(_grouped_conv(
            y, _f32(ms["proj_out"]["w"])[..., perm], 3 * c // head_dim))
    o = ops.linear_attention([s.reshape(n, h * w, 3 * c) for s in sources],
                             head_dim, impl=impl)
    o = _dense(o, p["to_out"]).reshape(n, h, w, c)
    return x + rms_norm(o, p["norm"])


def glu_mb_conv(x: jax.Array, p) -> jax.Array:
    """GLUMBConv: 1x1 expand to 8C, SiLU, depthwise 3x3, ``a * SiLU(g)``,
    1x1 back to C, RMSNorm, residual."""
    y = silu(_dense(x, p["conv_inverted"]))
    y = _grouped_conv(y, p["conv_depth"]["w"], y.shape[-1],
                      p["conv_depth"]["b"])
    a, g = jnp.split(y, 2, axis=-1)
    return x + rms_norm(_dense(a * silu(g), p["conv_point"]), p["norm"])


def up_block(x: jax.Array, p, impl: Optional[str] = None) -> jax.Array:
    from repro.kernels import ops
    y = ops.upsample_conv3x3(x, p["w"], p["b"], impl=impl)
    return y + channel_shortcut(x, y.shape[-1])


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_trunk(params: Dict[str, Any], z: jax.Array, cfg: DCAEConfig,
                  impl: Optional[str] = None) -> jax.Array:
    """latent -> the activation ahead of norm_out [N, 32h, 32w, C0]."""
    from repro.kernels import ops
    chs = cfg.block_out_channels
    z = z / cfg.scaling_factor
    with jax.named_scope("dcae.conv_in"):
        x = ops.conv3x3(z, params["conv_in"]["w"], params["conv_in"]["b"],
                        impl=impl)
        x = x + jnp.repeat(z, chs[-1] // cfg.latent_channels, axis=-1)
    for i, stage in zip(reversed(range(len(chs))), params["up"]):
        if "upsample" in stage:
            with jax.named_scope(f"dcae.s{i}.up"):
                x = up_block(x, stage["upsample"], impl)
        if cfg.block_types[i] == RES:
            with jax.named_scope(f"dcae.s{i}.res"):
                for blk in stage["blocks"]:
                    x = res_block(x, blk, impl)
        else:
            with jax.named_scope(f"dcae.s{i}.evit"):
                for blk in stage["blocks"]:
                    x = linear_attention_block(x, blk["attn"],
                                               cfg.attention_head_dim, impl)
                    x = glu_mb_conv(x, blk["mlp"])
    return x


@fp32_math
def decode(params: Dict[str, Any], z: jax.Array, cfg: DCAEConfig,
           impl: Optional[str] = None) -> jax.Array:
    """latent [N, h, w, C_lat] -> image [N, 32h, 32w, 3] (float)."""
    from repro.kernels import ops
    x = _decode_trunk(params, z, cfg, impl)
    x = jnp.maximum(rms_norm(x, params["norm_out"]), 0.0)
    return ops.conv3x3(x, params["conv_out"]["w"], params["conv_out"]["b"],
                       impl=impl)


@fp32_math
def decode_u8(params: Dict[str, Any], z: jax.Array, cfg: DCAEConfig,
              impl: Optional[str] = None) -> jax.Array:
    """latent [N, h, w, C_lat] -> uint8 image [N, 32h, 32w, 3]: the trunk,
    then RMSNorm + ReLU + conv_out + clamp + quantize
    (:func:`repro.kernels.ops.rms_output_epilogue`), so the decode's last
    write is the uint8 image."""
    from repro.kernels import ops
    x = _decode_trunk(params, z, cfg, impl)
    with jax.named_scope("dcae.out"):
        return ops.rms_output_epilogue(
            x, params["norm_out"]["scale"], params["norm_out"]["bias"],
            params["conv_out"]["w"], params["conv_out"]["b"], eps=RMS_EPS,
            impl=impl)
