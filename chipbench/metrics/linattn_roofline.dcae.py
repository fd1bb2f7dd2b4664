"""linattn_roofline.dcae: least time over device time, summed over the
``linear_attention`` kernel events of the DC-AE decode in the window, in
%.  A call's least time is the larger of its FLOPs (both products per
head) over the bf16 peak and its least bytes (q, k and v read once, the
output written once) over HBM bandwidth: bytes bound it.  Events are
matched to calls as in ``conv_roofline.dcae``."""

import os

from lib.cells import load_module

_conv = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "conv_roofline.dcae.py"),
                    "metric_conv_roofline_dcae")


def read(ctx):
    return _conv.roofline(ctx, ("linear_attention",))
