"""conv_roofline.dcae: least time over device time, summed over the DC-AE
decode's convolution kernel events in the window (``conv3x3`` for
``conv_in`` and every ResBlock conv, ``upsample_conv3x3`` for the up
blocks, ``rms_output_epilogue`` for ``conv_out``), in %.

Each event is matched to a call of ``ctx.calls`` (the family module's
``calls``) by the shapes in its HLO text, and its least time is the
larger of the call's FLOPs over the bf16 peak and its least bytes over
HBM bandwidth, padded slots included.  None unless every event matches.
:func:`roofline` and :func:`match` also serve ``linattn_roofline.dcae``.
"""

from lib import flops as F
from lib.profile import kind_of, shapes_of

CONV_KINDS = ("conv3x3", "upsample_conv3x3", "rms_output_epilogue")


def match(calls, kind: str, name: str):
    """(call, images) of one kernel event.  A banded conv's first 4-D
    operand is its row-banded input ``[N * bands, rows + 2, W + 2, Cin]``
    and its result's last axis ``Cout`` (``2 * Cout`` for the upsampler's
    interleaved output); the linear attention's result is ``[N, T,
    Cout]``."""
    shapes = [s for _, s in shapes_of(name)]
    if not shapes:
        return None
    result = shapes[0]
    if kind == "linear_attention":
        if len(result) != 3:
            return None
        n, tokens, cout = result
        for c in calls:
            if c.kernel == kind and c.h * c.h == tokens and c.cout == cout:
                return c, n
        return None
    band = next((s for s in shapes[1:] if len(s) == 4), None)
    if band is None:
        return None
    width, cin = band[2] - 2, band[3]
    cout = result[-1] // (2 if kind == "upsample_conv3x3" else 1)
    rows_total = band[0] * (band[1] - 2)
    if width <= 0 or rows_total % width:
        return None
    for c in calls:
        if (c.kernel == kind and c.h == width and c.cin == cin
                and c.cout == cout):
            return c, rows_total // width
    return None


def roofline(ctx, kinds):
    if ctx.trace is None or ctx.peaks is None:
        return None
    least = spent = 0.0
    for op in ctx.trace.kernel_ops(tuple(kinds)):
        if op.name.split(" = ", 1)[-1].startswith("("):
            continue                        # a tuple result: not the kernel
        hit = match(ctx.calls, kind_of(op.name), op.name)
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


def read(ctx):
    return roofline(ctx, CONV_KINDS)
