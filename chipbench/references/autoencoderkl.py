"""Plain reference of the AutoencoderKL decoder (the VAE of Stable
Diffusion 1.5, SD3.5 and FLUX.1), written from the published architecture
(diffusers ``Decoder``) in straightforward ``jax.numpy``.

    z -> z / scaling_factor + shift_factor -> [post_quant_conv 1x1]
      -> conv_in 3x3 -> mid (res, attn, res)
      -> up levels, top width first: layers_per_block + 1 res blocks,
         then nearest 2x + conv 3x3 (all but the last level)
      -> GroupNorm + SiLU -> conv_out 3x3 -> image in [-1, 1]

A res block is ``x + conv2(silu(gn2(conv1(silu(gn1(x))))))`` with a 1x1
shortcut conv where the widths differ; the mid attention is one head over
the latent grid, GroupNorm in front, ``softmax(q k^T / sqrt(C)) v`` and an
output projection, added to its input.  GroupNorm uses eps 1e-6 with
fp32 statistics.  Served bytes are ``round((clip(y, -1, 1) + 1) * 127.5)``.

Layout: NHWC activations, HWIO conv weights, ``[in, out]`` dense
weights.  Parameters are the benchmark's own (``chipbench/lib/weights``),
never read from the system under test.  ``precision="float32"`` runs
every product at full fp32 (``Precision.HIGHEST``); ``"bfloat16"`` holds
weights and activations in bf16 with default products — the control.

A decoder family enters the benchmark as one such module under
``chipbench/references/``, named by a configuration's ``reference``; the
harness's shared files (``run.py``, ``lib/``) name no family.  Besides
``decode``, ``to_u8`` and ``compiled``, a family module provides:

* ``PROGRAM_KEYS``: the program decoder's attribute -> the architecture
  key it must equal (``run.py`` refuses a run where one differs);
* ``latent_hwc(arch)``: the stored latent's (h, w, C) at the
  configuration's ``resolution``;
* ``weight_tree(arch, init, normal)``: the parameter tree, each leaf the
  value of ``normal(shape, std, mean=0.0)``, called in tree order
  (``lib/weights.py`` fills it from one draw);
* ``calls(arch)``: every matmul-bearing call of one decode for one image,
  in program order (``lib/flops.py:Call``), read by the per-layer
  metrics.

The architecture also states ``resolution`` and ``out_channels``, which
``run.py`` reads for the served image's size.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lib.flops import F32, Call, conv_call

EPS = 1e-6

#: The program decoder's sizes that must agree with the configuration.
PROGRAM_KEYS = {"latent_channels": "latent_channels",
                "block_out_channels": "block_out_channels",
                "layers_per_block": "layers_per_block",
                "groups": "norm_num_groups",
                "scaling_factor": "scaling_factor",
                "shift_factor": "shift_factor"}


def _settings(precision: str):
    if precision == "float32":
        return jnp.float32, jax.lax.Precision.HIGHEST
    if precision == "bfloat16":
        return jnp.bfloat16, jax.lax.Precision.DEFAULT
    raise ValueError(f"precision must be float32 or bfloat16: {precision!r}")


def _conv(x, p, dtype, prec):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(dtype), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
        preferred_element_type=jnp.float32)
    return (y + p["b"].astype(jnp.float32)).astype(dtype)


def _group_norm(x, p, groups, dtype):
    n, h, w, c = x.shape
    xf = x.astype(jnp.float32).reshape(n, h * w, groups, c // groups)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = jnp.square(xf - mean).mean(axis=(1, 3), keepdims=True)
    xf = ((xf - mean) / jnp.sqrt(var + EPS)).reshape(n, h, w, c)
    xf = xf * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return xf.astype(dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _resnet(x, p, groups, dtype, prec):
    h = _conv(_silu(_group_norm(x, p["norm1"], groups, dtype)), p["conv1"],
              dtype, prec)
    h = _conv(_silu(_group_norm(h, p["norm2"], groups, dtype)), p["conv2"],
              dtype, prec)
    if "shortcut" in p:
        x = _conv(x, p["shortcut"], dtype, prec)
    return x + h


def _dense(x, p, dtype, prec):
    y = jnp.einsum("ntc,cd->ntd", x, p["w"].astype(dtype), precision=prec,
                   preferred_element_type=jnp.float32)
    return (y + p["b"].astype(jnp.float32)).astype(dtype)


def _attention(x, p, groups, dtype, prec):
    n, h, w, c = x.shape
    y = _group_norm(x, p["norm"], groups, dtype).reshape(n, h * w, c)
    q = _dense(y, p["q"], dtype, prec)
    k = _dense(y, p["k"], dtype, prec)
    v = _dense(y, p["v"], dtype, prec)
    logits = jnp.einsum("nqc,nkc->nqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(c)
    attn = jax.nn.softmax(logits, axis=-1).astype(dtype)
    o = jnp.einsum("nqk,nkc->nqc", attn, v, precision=prec,
                   preferred_element_type=jnp.float32).astype(dtype)
    o = _dense(o, p["proj"], dtype, prec)
    return x + o.reshape(n, h, w, c)


def _upsample(x, p, dtype, prec):
    x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return _conv(x, p["conv"], dtype, prec)


def decode(params: Dict[str, Any], z: jax.Array, arch: Dict[str, Any],
           precision: str = "float32") -> jax.Array:
    """latent [N, h, w, C] -> float image [N, 8h, 8w, 3] (fp32)."""
    dtype, prec = _settings(precision)
    groups = arch["norm_num_groups"]
    x = z.astype(jnp.float32) / arch["scaling_factor"]
    x = (x + (arch.get("shift_factor") or 0.0)).astype(dtype)
    if arch.get("use_post_quant_conv"):
        x = _conv(x, params["post_quant_conv"], dtype, prec)
    x = _conv(x, params["conv_in"], dtype, prec)
    x = _resnet(x, params["mid"]["res1"], groups, dtype, prec)
    x = _attention(x, params["mid"]["attn"], groups, dtype, prec)
    x = _resnet(x, params["mid"]["res2"], groups, dtype, prec)
    for level in params["up"]:
        for blk in level["blocks"]:
            x = _resnet(x, blk, groups, dtype, prec)
        if "upsample" in level:
            x = _upsample(x, level["upsample"], dtype, prec)
    x = _silu(_group_norm(x, params["norm_out"], groups, dtype))
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
    """The latent of one image: a 2x upsampler between every two levels."""
    s = arch["resolution"] // 2 ** (len(arch["block_out_channels"]) - 1)
    return (s, s, arch["latent_channels"])


def weight_tree(arch: Dict[str, Any], init: Dict[str, float], normal):
    """The decoder's parameter tree, leaf by leaf from ``normal``.

    Convolutions and dense layers draw N(0, 1 / fan_in), biases and
    GroupNorm affines are small and non-zero so that every parameter shows
    in the output, and ``conv_out`` is scaled by ``init["out_gain"]`` so
    that served images sit inside the display range instead of saturating
    it (a trained decoder's outputs do)."""

    def conv(k, cin, cout, gain=1.0):
        return {"w": normal((k, k, cin, cout), gain / np.sqrt(k * k * cin)),
                "b": normal((cout,), init["bias_std"])}

    def gn(c):
        return {"scale": normal((c,), init["norm_affine_std"], 1.0),
                "bias": normal((c,), init["norm_affine_std"])}

    def dense(cin, cout):
        return {"w": normal((cin, cout), 1.0 / np.sqrt(cin)),
                "b": normal((cout,), init["bias_std"])}

    def resblock(cin, cout):
        p = {"norm1": gn(cin), "conv1": conv(3, cin, cout),
             "norm2": gn(cout), "conv2": conv(3, cout, cout)}
        if cin != cout:
            p["shortcut"] = conv(1, cin, cout)
        return p

    chs = list(arch["block_out_channels"])
    top = chs[-1]
    tree = {"conv_in": conv(3, arch["latent_channels"], top),
            "mid": {"res1": resblock(top, top),
                    "attn": {"norm": gn(top), "q": dense(top, top),
                             "k": dense(top, top), "v": dense(top, top),
                             "proj": dense(top, top)},
                    "res2": resblock(top, top)},
            "up": []}
    cin = top
    for i, cout in enumerate(reversed(chs)):
        blocks = []
        for _ in range(arch["layers_per_block"] + 1):
            blocks.append(resblock(cin, cout))
            cin = cout
        level = {"blocks": blocks}
        if i < len(chs) - 1:
            level["upsample"] = {"conv": conv(3, cout, cout)}
        tree["up"].append(level)
    tree["norm_out"] = gn(chs[0])
    tree["conv_out"] = conv(3, chs[0], arch["out_channels"],
                            gain=init["out_gain"])
    return tree


def calls(arch: Dict[str, Any]) -> List[Call]:
    """Every matmul-bearing call of one decode, in program order, for one
    image of ``arch["resolution"]`` pixels a side.

    The decode is a chain of 3x3 convolutions (``conv3x3``: ``conv_in``,
    both convs of every res block with GroupNorm + SiLU fused in front,
    and the uint8 output epilogue), phase-decomposed 2x upsampler
    convolutions (``upsample_conv3x3``: each of the four output phases is
    a 2x2 collapsed filter over the pre-upsample grid, 16 taps in all
    instead of the 36 of a 3x3 conv over the upsampled tensor), 1x1
    shortcut convs and a single-head attention over the latent grid in
    the mid block."""
    chs = list(reversed(arch["block_out_channels"]))
    groups_layers = arch["layers_per_block"] + 1
    h = latent_hwc(arch)[0]
    top = chs[0]
    out = [conv_call("conv_in", h, h, arch["latent_channels"], top)]

    def resblock(tag, cin, cout, hh):
        res = [conv_call(f"{tag}.conv1", hh, hh, cin, cout),
               conv_call(f"{tag}.conv2", hh, hh, cout, cout)]
        if cin != cout:
            res.append(conv_call(f"{tag}.shortcut", hh, hh, cin, cout, k=1,
                                 kernel="xla"))
        return res

    out += resblock("mid.res1", top, top, h)
    n = h * h
    out.append(Call("xla", "mid.attn.proj", h, top, top,
                    4 * 2.0 * n * top * top,
                    5 * n * top * F32, 4 * (top * top + top) * F32))
    out.append(Call("flash_attention", "mid.attn.core", h, top, top,
                    4.0 * n * n * top,
                    4 * n * top * F32, 0.0))
    out += resblock("mid.res2", top, top, h)
    cin = top
    for i, cout in enumerate(chs):
        for j in range(groups_layers):
            out += resblock(f"up{i}.res{j}", cin, cout, h)
            cin = cout
        if i < len(chs) - 1:
            out.append(Call(
                "upsample_conv3x3", f"up{i}.upsample", h, cout, cout,
                2.0 * (2 * h) * (2 * h) * cout * cout * 4,
                h * h * cout * F32 + 4 * h * h * cout * F32,
                (9 * cout * cout + cout) * F32))
            h *= 2
    out.append(conv_call("conv_out", h, h, chs[-1], arch["out_channels"],
                         out_itemsize=1))
    return out
