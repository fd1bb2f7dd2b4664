"""Operations and bytes of the SD-family VAE decoder, per call and per
image, from the configuration's shapes alone.

The decode is a chain of 3x3 convolutions (``conv3x3``: ``conv_in``,
both convs of every res block with GroupNorm + SiLU fused in front, and
the uint8 output epilogue), phase-decomposed 2x upsampler convolutions
(``upsample_conv3x3``: each of the four output phases is a 2x2
collapsed filter over the pre-upsample grid, 16 taps in all instead of
the 36 of a 3x3 conv over the upsampled tensor), 1x1 shortcut convs and a
single-head attention over the latent grid in the mid block.

Bytes are the least the algorithm must move for a call: its input read
once, its weights read once and its output written once, at the stored
widths (fp32 activations and weights, uint8 for the epilogue's output).
Counted so, no implementation of a call can beat its roofline.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

F32 = 4


@dataclasses.dataclass(frozen=True)
class Call:
    kernel: str          # "conv3x3", "upsample_conv3x3", "flash_attention"
                         # (Pallas kernels) or "xla"
    what: str
    h: int               # input rows (= columns) of one image
    cin: int
    cout: int
    flops: float         # per image
    act_bytes: float     # activation bytes per image (read + written)
    weight_bytes: float  # read once per call, whatever the batch

    def bytes_for(self, batch: int) -> float:
        return batch * self.act_bytes + self.weight_bytes


def _conv(what: str, h: int, w: int, cin: int, cout: int, k: int = 3,
          kernel: str = "conv3x3", out_itemsize: int = F32) -> Call:
    return Call(kernel, what, h, cin, cout, 2.0 * h * w * cin * cout * k * k,
                h * w * cin * F32 + h * w * cout * out_itemsize,
                (k * k * cin * cout + cout) * F32)


def decoder_calls(arch: Dict) -> List[Call]:
    """Every matmul-bearing call of one decode, in program order, for one
    image of ``arch["resolution"]`` pixels a side."""
    chs = list(reversed(arch["block_out_channels"]))
    groups_layers = arch["layers_per_block"] + 1
    h = arch["resolution"] // 2 ** (len(chs) - 1)
    top = chs[0]
    calls = [_conv("conv_in", h, h, arch["latent_channels"], top)]

    def resblock(tag, cin, cout, hh):
        out = [_conv(f"{tag}.conv1", hh, hh, cin, cout),
               _conv(f"{tag}.conv2", hh, hh, cout, cout)]
        if cin != cout:
            out.append(_conv(f"{tag}.shortcut", hh, hh, cin, cout, k=1,
                             kernel="xla"))
        return out

    calls += resblock("mid.res1", top, top, h)
    n = h * h
    calls.append(Call("xla", "mid.attn.proj", h, top, top,
                      4 * 2.0 * n * top * top,
                      5 * n * top * F32, 4 * (top * top + top) * F32))
    calls.append(Call("flash_attention", "mid.attn.core", h, top, top,
                      4.0 * n * n * top,
                      4 * n * top * F32, 0.0))
    calls += resblock("mid.res2", top, top, h)
    cin = top
    for i, cout in enumerate(chs):
        for j in range(groups_layers):
            calls += resblock(f"up{i}.res{j}", cin, cout, h)
            cin = cout
        if i < len(chs) - 1:
            calls.append(Call(
                "upsample_conv3x3", f"up{i}.upsample", h, cout, cout,
                2.0 * (2 * h) * (2 * h) * cout * cout * 4,
                h * h * cout * F32 + 4 * h * h * cout * F32,
                (9 * cout * cout + cout) * F32))
            h *= 2
    calls.append(_conv("conv_out", h, h, chs[-1], arch["out_channels"],
                       out_itemsize=1))
    return calls


def decode_flops_per_image(arch: Dict) -> float:
    return sum(c.flops for c in decoder_calls(arch))


def least_seconds(call: Call, batch: int, peak_flops: float,
                  peak_bytes: float) -> float:
    """The roofline's least time of one call over ``batch`` images."""
    return max(call.flops * batch / peak_flops,
               call.bytes_for(batch) / peak_bytes)
