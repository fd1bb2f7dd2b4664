"""Operations and bytes of a decoder's calls, per call and per image,
from shapes alone.

A decoder family's reference module lists the matmul-bearing calls of
one decode in program order (``calls(arch) -> list[Call]``, beside its
``decode``); this module holds what every family shares: the record of
one call, the count of a convolution, the sum over a decode and the
roofline's least time.

Bytes are the least the algorithm must move for a call: its input read
once, its weights read once and its output written once, at the stored
widths (fp32 activations and weights, uint8 for an output epilogue).
Counted so, no implementation of a call can beat its roofline.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

F32 = 4


@dataclasses.dataclass(frozen=True)
class Call:
    kernel: str          # the Pallas kernel that runs it ("conv3x3",
                         # "upsample_conv3x3", "flash_attention") or "xla"
    what: str
    h: int               # input rows (= columns) of one image
    cin: int
    cout: int
    flops: float         # per image
    act_bytes: float     # activation bytes per image (read + written)
    weight_bytes: float  # read once per call, whatever the batch

    def bytes_for(self, batch: int) -> float:
        return batch * self.act_bytes + self.weight_bytes


def conv_call(what: str, h: int, w: int, cin: int, cout: int, k: int = 3,
              kernel: str = "conv3x3", out_itemsize: int = F32) -> Call:
    """A k x k convolution with bias over an ``h`` x ``w`` image, stride 1,
    same padding."""
    return Call(kernel, what, h, cin, cout, 2.0 * h * w * cin * cout * k * k,
                h * w * cin * F32 + h * w * cout * out_itemsize,
                (k * k * cin * cout + cout) * F32)


def flops_per_image(calls: Iterable[Call]) -> float:
    return sum(c.flops for c in calls)


def least_seconds(call: Call, batch: int, peak_flops: float,
                  peak_bytes: float) -> float:
    """The roofline's least time of one call over ``batch`` images."""
    return max(call.flops * batch / peak_flops,
               call.bytes_for(batch) / peak_bytes)
