"""Plain reference of the DC-AE decoder (diffusers ``AutoencoderDC``, the
VAE of SANA 1.0), written from the published architecture in
straightforward ``jax.numpy``.

    z -> z / scaling_factor
      -> conv_in 3x3 + repeat_interleave(z, C_top / C_latent) (channels)
      -> stages, deepest first: an up block (all but the deepest), then
         decoder_layers_per_block ResBlocks or EfficientViT blocks
      -> RMSNorm -> ReLU -> conv_out 3x3 -> image in [-1, 1]

* ResBlock: ``x + RMSNorm(conv2(SiLU(conv1(x))))``, conv2 without bias.
* Up block (``DCUpBlock2d``, ``interpolate``, shortcut): ``conv3x3(
  nearest_2x(x)) + pixel_shuffle(repeat_interleave(x, 4 Cout / Cin), 2)``.
* EfficientViT block: ``SanaMultiscaleLinearAttention`` with a residual,
  then ``GLUMBConv`` with a residual.  The attention concatenates
  ``to_q(x)``, ``to_k(x)``, ``to_v(x)`` (no bias) into 3C channels,
  appends for each multiscale kernel size a depthwise kxk conv followed by
  a grouped 1x1 conv of ``attention_head_dim`` channels a group (no
  bias), reshapes the channels as heads of ``3 * attention_head_dim``
  consecutive channels split into q, k, v, applies ReLU to q and k, and
  computes per head ``o = ([v; 1] k^T) q`` in fp32, then ``o[:d] / (o[d]
  + 1e-15)``; ``to_out`` (no bias), RMSNorm, plus the input.  (The
  quadratic form for H*W <= head_dim never occurs at these sizes.)
  GLUMBConv: 1x1 to 8C with bias, SiLU, depthwise 3x3 with bias, split
  into ``a | g``, ``a * SiLU(g)``, 1x1 4C -> C without bias, RMSNorm,
  plus the input.
* RMSNorm: ``x * rsqrt(mean(x^2) + 1e-5) * scale + bias`` over channels
  with fp32 statistics.

Served bytes are ``round((clip(y, -1, 1) + 1) * 127.5)``.

Layout: NHWC activations, HWIO conv weights (``[k, k, Cin / groups,
Cout]``), ``[in, out]`` dense weights.  Parameters are the benchmark's own
(``chipbench/lib/weights``), never read from the system under test.
``precision="float32"`` runs every product at full fp32
(``Precision.HIGHEST``); ``"bfloat16"`` holds weights and activations in
bf16 with default products (the control).

The family module's names are those of ``autoencoderkl.py``: ``decode``,
``to_u8``, ``compiled``, ``PROGRAM_KEYS``, ``latent_hwc``,
``weight_tree`` and ``calls``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lib.flops import F32, Call, conv_call

RMS_EPS = 1e-5
ATTN_EPS = 1e-15
RES, EVIT = "ResBlock", "EfficientViTBlock"

#: The program decoder's sizes that must agree with the configuration.
PROGRAM_KEYS = {"latent_channels": "latent_channels",
                "block_out_channels": "decoder_block_out_channels",
                "block_types": "decoder_block_types",
                "layers_per_block": "decoder_layers_per_block",
                "qkv_multiscales": "decoder_qkv_multiscales",
                "attention_head_dim": "attention_head_dim",
                "norm_type": "decoder_norm_types",
                "act_fn": "decoder_act_fns",
                "upsample_block_type": "upsample_block_type",
                "scaling_factor": "scaling_factor"}


def _settings(precision: str):
    if precision == "float32":
        return jnp.float32, jax.lax.Precision.HIGHEST
    if precision == "bfloat16":
        return jnp.bfloat16, jax.lax.Precision.DEFAULT
    raise ValueError(f"precision must be float32 or bfloat16: {precision!r}")


def _conv(x, p, dtype, prec, groups=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(dtype), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=prec,
        preferred_element_type=jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(dtype)


def _dense(x, p, dtype, prec):
    y = jnp.einsum("nhwc,cd->nhwd", x, p["w"].astype(dtype), precision=prec,
                   preferred_element_type=jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(dtype)


def _rms_norm(x, p, dtype):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf / jnp.sqrt(var + RMS_EPS)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _resblock(x, p, dtype, prec):
    h = _conv(x, p["conv1"], dtype, prec)
    h = _conv(_silu(h), p["conv2"], dtype, prec)
    return x + _rms_norm(h, p["norm"], dtype)


def _pixel_shuffle(x):
    """[N, H, W, 4C] -> [N, 2H, 2W, C]: torch's ``pixel_shuffle(., 2)``,
    whose input channel ``4c + 2i + j`` lands on ``(2h + i, 2w + j)``."""
    n, h, w, c4 = x.shape
    c = c4 // 4
    x = x.reshape(n, h, w, c, 2, 2)
    return jnp.einsum("nhwcij->nhiwjc", x).reshape(n, 2 * h, 2 * w, c)


def _up_block(x, p, dtype, prec):
    cin, cout = x.shape[-1], p["w"].shape[-1]
    y = _conv(jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2), p, dtype,
              prec)
    short = _pixel_shuffle(jnp.repeat(x, 4 * cout // cin, axis=-1))
    return y + short


def _linear_attention(x, p, head_dim, dtype, prec):
    n, h, w, c = x.shape
    if h * w <= head_dim:
        raise ValueError("quadratic attention branch (H*W <= head dim) is "
                         "not part of this reference")
    qkv = jnp.concatenate([_dense(x, p[k], dtype, prec)
                           for k in ("to_q", "to_k", "to_v")], axis=-1)
    scales = [qkv]
    for ms in p["multiscale"]:
        y = _conv(qkv, ms["proj_in"], dtype, prec, groups=3 * c)
        scales.append(_conv(y, ms["proj_out"], dtype, prec,
                            groups=3 * c // head_dim))
    y = jnp.concatenate(scales, axis=-1).astype(jnp.float32)
    # heads of 3 * head_dim consecutive channels: q | k | v
    y = y.reshape(n, h * w, -1, 3, head_dim)
    q = jnp.maximum(y[:, :, :, 0], 0.0)
    k = jnp.maximum(y[:, :, :, 1], 0.0)
    v = y[:, :, :, 2]
    v1 = jnp.concatenate([v, jnp.ones_like(v[..., :1])], axis=-1)
    scores = jnp.einsum("ntha,nthb->nhab", v1, k, precision=prec,
                        preferred_element_type=jnp.float32)
    o = jnp.einsum("nhab,nthb->ntha", scores, q, precision=prec,
                   preferred_element_type=jnp.float32)
    o = o[..., :head_dim] / (o[..., head_dim:] + ATTN_EPS)
    o = o.reshape(n, h, w, -1).astype(dtype)
    o = _dense(o, p["to_out"], dtype, prec)
    return x + _rms_norm(o, p["norm"], dtype)


def _glu_mb_conv(x, p, dtype, prec):
    y = _silu(_dense(x, p["conv_inverted"], dtype, prec))
    y = _conv(y, p["conv_depth"], dtype, prec, groups=y.shape[-1])
    a, g = jnp.split(y, 2, axis=-1)
    y = _dense(a * _silu(g), p["conv_point"], dtype, prec)
    return x + _rms_norm(y, p["norm"], dtype)


def decode(params: Dict[str, Any], z: jax.Array, arch: Dict[str, Any],
           precision: str = "float32") -> jax.Array:
    """latent [N, h, w, C] -> float image [N, 32h, 32w, 3] (fp32)."""
    dtype, prec = _settings(precision)
    chs = list(arch["decoder_block_out_channels"])
    types = list(arch["decoder_block_types"])
    head_dim = arch["attention_head_dim"]
    z = (z.astype(jnp.float32) / arch["scaling_factor"]).astype(dtype)
    x = _conv(z, params["conv_in"], dtype, prec)
    x = x + jnp.repeat(z, chs[-1] // z.shape[-1], axis=-1)
    for i, stage in zip(reversed(range(len(chs))), params["up"]):
        if "upsample" in stage:
            x = _up_block(x, stage["upsample"], dtype, prec)
        for blk in stage["blocks"]:
            if types[i] == RES:
                x = _resblock(x, blk, dtype, prec)
            else:
                x = _linear_attention(x, blk["attn"], head_dim, dtype, prec)
                x = _glu_mb_conv(x, blk["mlp"], dtype, prec)
    x = jnp.maximum(_rms_norm(x, params["norm_out"], dtype), 0)
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


def latent_hwc(arch: Dict[str, Any]) -> Tuple[int, int, int]:
    """The latent of one image: a 2x up block between every two stages."""
    s = arch["resolution"] // 2 ** (len(arch["decoder_block_out_channels"])
                                    - 1)
    return (s, s, arch["latent_channels"])


def weight_tree(arch: Dict[str, Any], init: Dict[str, float], normal):
    """The decoder's parameter tree, leaf by leaf from ``normal``.

    Convolutions and dense layers draw N(0, 1 / fan_in) (a depthwise conv's
    fan-in is its k*k taps, a grouped 1x1's its group width), biases and
    RMSNorm affines are small and non-zero so that every parameter shows
    in the output, and ``conv_out`` is scaled by ``init["out_gain"]`` so
    that served images sit inside the display range instead of saturating
    it.  Stages are listed in decode order, deepest first; each holds its
    ``upsample`` (all but the deepest) and its ``blocks``."""
    d = arch["attention_head_dim"]

    def conv(k, cin, cout, bias=True, groups=1, gain=1.0):
        fan_in = k * k * cin // groups
        p = {"w": normal((k, k, cin // groups, cout), gain / np.sqrt(fan_in))}
        if bias:
            p["b"] = normal((cout,), init["bias_std"])
        return p

    def dense(cin, cout, bias=False):
        p = {"w": normal((cin, cout), 1.0 / np.sqrt(cin))}
        if bias:
            p["b"] = normal((cout,), init["bias_std"])
        return p

    def norm(c):
        return {"scale": normal((c,), init["norm_affine_std"], 1.0),
                "bias": normal((c,), init["norm_affine_std"])}

    def block(kind, c, scales):
        if kind == RES:
            return {"conv1": conv(3, c, c), "conv2": conv(3, c, c, False),
                    "norm": norm(c)}
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

    chs = list(arch["decoder_block_out_channels"])
    tree = {"conv_in": conv(3, arch["latent_channels"], chs[-1]), "up": []}
    for i in reversed(range(len(chs))):
        stage = {}
        if i < len(chs) - 1:
            stage["upsample"] = conv(3, chs[i + 1], chs[i])
        stage["blocks"] = [block(arch["decoder_block_types"][i], chs[i],
                                 list(arch["decoder_qkv_multiscales"][i]))
                           for _ in range(arch["decoder_layers_per_block"][i])]
        tree["up"].append(stage)
    tree["norm_out"] = norm(chs[0])
    tree["conv_out"] = conv(3, chs[0], arch["out_channels"],
                            gain=init["out_gain"])
    return tree


def _dense_call(what, h, cin, cout, bias=False):
    n = h * h
    return Call("xla", what, h, cin, cout, 2.0 * n * cin * cout,
                n * (cin + cout) * F32, (cin * cout + bias * cout) * F32)


def _depthwise_call(what, h, c, k, groups, bias=False):
    """A grouped kxk conv of ``c`` channels in ``groups`` groups."""
    n = h * h
    per = c // groups
    return Call("xla", what, h, c, c, 2.0 * n * c * per * k * k,
                2 * n * c * F32, (k * k * per * c + bias * c) * F32)


def calls(arch: Dict[str, Any]) -> List[Call]:
    """Every matmul-bearing call of one decode, in program order, for one
    image of ``arch["resolution"]`` pixels a side, each tagged with the
    kernel that runs it: ``conv3x3`` (``conv_in``, both convs of every
    ResBlock), ``upsample_conv3x3`` (each up block's conv, counted
    phase-decomposed: four 2x2 collapsed filters over the pre-upsample
    grid, 16 taps where a 3x3 conv over the upsampled tensor takes 36),
    ``linear_attention`` (each EfficientViT block's attention core over
    all its heads), ``rms_output_epilogue`` (``conv_out``, uint8 out) and
    ``xla`` (the 1x1 projections, the depthwise and grouped convs).

    The linear attention's FLOPs are its two products per head, ``[v; 1]
    k^T`` and its product with ``q``: ``2 * 2 (d + 1) d`` per token and
    head; its least bytes read q, k and v once (``6C`` channels: the
    projections and their multiscale aggregate) and write the ``2C``
    output once."""
    chs = list(arch["decoder_block_out_channels"])
    types = list(arch["decoder_block_types"])
    d = arch["attention_head_dim"]
    h = latent_hwc(arch)[0]
    out = [conv_call("conv_in", h, h, arch["latent_channels"], chs[-1])]
    for i in reversed(range(len(chs))):
        c = chs[i]
        if i < len(chs) - 1:
            cin = chs[i + 1]
            out.append(Call(
                "upsample_conv3x3", f"s{i}.upsample", h, cin, c,
                2.0 * (2 * h) * (2 * h) * cin * c * 4,
                h * h * cin * F32 + 4 * h * h * c * F32,
                (9 * cin * c + c) * F32))
            h *= 2
        scales = list(arch["decoder_qkv_multiscales"][i])
        for j in range(arch["decoder_layers_per_block"][i]):
            tag = f"s{i}.b{j}"
            if types[i] == RES:
                out += [conv_call(f"{tag}.conv1", h, h, c, c),
                        dataclasses.replace(    # conv2 has no bias
                            conv_call(f"{tag}.conv2", h, h, c, c),
                            weight_bytes=9 * c * c * F32)]
                continue
            n, src = h * h, 1 + len(scales)
            out.append(_dense_call(f"{tag}.to_qkv", h, c, 3 * c))
            for k in scales:
                out += [_depthwise_call(f"{tag}.ms{k}.proj_in", h, 3 * c, k,
                                        3 * c),
                        _depthwise_call(f"{tag}.ms{k}.proj_out", h, 3 * c, 1,
                                        3 * c // d)]
            heads = src * c // d
            out.append(Call("linear_attention", f"{tag}.attn", h, 3 * src * c,
                            src * c, 4.0 * (d + 1) * d * n * heads,
                            (3 * src * c + src * c) * n * F32, 0.0))
            out += [_dense_call(f"{tag}.to_out", h, src * c, c),
                    _dense_call(f"{tag}.conv_inverted", h, c, 8 * c, True),
                    _depthwise_call(f"{tag}.conv_depth", h, 8 * c, 3, 8 * c,
                                    True),
                    _dense_call(f"{tag}.conv_point", h, 4 * c, c)]
    out.append(conv_call("conv_out", h, h, chs[0], arch["out_channels"],
                         kernel="rms_output_epilogue", out_itemsize=1))
    return out
