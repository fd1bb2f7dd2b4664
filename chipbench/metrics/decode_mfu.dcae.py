"""decode_mfu.dcae: FLOPs of the real images decoded in the window (the
family module's ``calls``) over the device time of the DC-AE decode
program's executions times the chip's bf16 peak, in %.  The decode
program is the one whose operations include the ``linear_attention``
kernel."""

from lib.profile import kind_of


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    images = sum(c.batcher["decodes"] for c in ctx.window.calls)
    execs = ctx.trace.module_executions(
        lambda o: kind_of(o.name) == "linear_attention")
    seconds = sum(m.seconds for m, _ in execs)
    if images == 0 or seconds <= 0:
        return None
    return 100.0 * images * ctx.flops_per_image / (
        seconds * ctx.peaks["bf16_flops_per_s"])
