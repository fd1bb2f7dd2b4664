"""The per-layer metric readers' arithmetic.  Each file under
``chipbench/metrics/`` names one of these as its ``read``; a reader
returns None where it finds nothing to read, and the metric is then left
out of the result line.

``ctx`` carries the cell, the measured ``window`` (served results and the
batcher's counters per call), the reduced profiler ``trace`` (None
without one), the chip's ``peaks``, the decoder's ``calls`` and
``flops_per_image`` (``chipbench/lib/flops.py``).
"""

from __future__ import annotations

from typing import Optional

from lib import flops as F
from lib.check import DECODE_CLASSES
from lib.profile import kind_of, shapes_of

#: The decode's convolution kernels as the device trace names them (the
#: Pallas call's op name), by the kernel each runs: ``_conv_kernel`` for
#: the plain, GroupNorm-fused and uint8-epilogue convs,
#: ``_upsample_conv_kernel`` for the upsamplers.
CONV_KERNELS = {"conv3x3": "conv3x3", "gn_silu_conv3x3": "conv3x3",
                "output_epilogue": "conv3x3",
                "upsample_conv3x3": "upsample_conv3x3"}


def decode_share(ctx) -> Optional[float]:
    """Share of the window's served requests whose hit class needs a
    decode (latent hit, full miss, regeneration), in %."""
    served = [r for r in ctx.window.results if r is not None]
    if not served:
        return None
    return 100.0 * sum(r.hit_class in DECODE_CLASSES
                       for r in served) / len(served)


def padded_slot_share(ctx) -> Optional[float]:
    """Padded batch slots over all decoded slots in the window, in %."""
    padded = sum(c.batcher["padded_slots"] for c in ctx.window.calls)
    real = sum(c.batcher["decodes"] for c in ctx.window.calls)
    if real == 0:
        return None
    return 100.0 * padded / (real + padded)


def decode_mfu(ctx) -> Optional[float]:
    """FLOPs of the real images decoded in the window over the device time
    of the decode program's executions times the chip's bf16 peak, in %."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    images = sum(c.batcher["decodes"] for c in ctx.window.calls)
    execs = ctx.trace.module_executions(
        lambda o: kind_of(o.name) in CONV_KERNELS)
    seconds = sum(m.seconds for m, _ in execs)
    if images == 0 or seconds <= 0:
        return None
    return 100.0 * images * ctx.flops_per_image / (
        seconds * ctx.peaks["bf16_flops_per_s"])


def _match(call_list, kind: str, name: str):
    """(call, images) of one convolution kernel event, from the shapes in
    its HLO text: the first operand is the row-banded input
    ``[N * bands, rows + 2, W + 2, Cin]`` and the result's last axis is
    ``Cout`` (``2 * Cout`` for the upsampler's interleaved output)."""
    shapes = [s for _, s in shapes_of(name)]
    if len(shapes) < 2:
        return None
    result = shapes[0]
    band = next((s for s in shapes[1:] if len(s) == 4), None)
    if band is None:
        return None
    width, cin = band[2] - 2, band[3]
    kernel = CONV_KERNELS[kind]
    cout = result[-1] // (2 if kernel == "upsample_conv3x3" else 1)
    rows_total = band[0] * (band[1] - 2)
    if width <= 0 or rows_total % width:
        return None
    images = rows_total // width
    for c in call_list:
        if (c.kernel == kernel and c.h == width and c.cin == cin
                and c.cout == cout):
            return c, images
    return None


def conv_events(trace):
    """The convolution kernels' events: the ops named in
    :data:`CONV_KERNELS` whose result is one array.  A GroupNorm-fused
    conv op also runs a statistics kernel (``_channel_sums_kernel``,
    a tuple of per-channel sums), which is not a convolution."""
    return [op for op in trace.kernel_ops(tuple(CONV_KERNELS))
            if not op.name.split(" = ", 1)[-1].startswith("(")]


def conv_roofline(ctx) -> Optional[float]:
    """Least time over device time, summed over the events of the
    convolution kernels (``_conv_kernel``, ``_upsample_conv_kernel``) in
    the window, in %.  A call's least time is the larger of its
    FLOPs over the bf16 peak and its bytes over HBM bandwidth, padded
    slots included.  None unless every event's shapes match a call of
    the decoder."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    least = spent = 0.0
    for op in conv_events(ctx.trace):
        hit = _match(ctx.calls, kind_of(op.name), op.name)
        if hit is None:
            return None
        call, images = hit
        least += F.least_seconds(call, images,
                                 ctx.peaks["bf16_flops_per_s"],
                                 ctx.peaks["hbm_bytes_per_s"])
        spent += op.seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent


def idle_share(ctx) -> Optional[float]:
    """1 - busy union over the traced window, in %."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
