"""Kernels (estimation): ``multi_agg``'s share of its roofline over the
traced batches — logical bytes and operations of every moment pass the
traced dashboard batches made, over the device time of the kernel."""

from svcbench import devtrace, roofline

MODULES = ("multi_agg_tiles",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = devtrace.module_time(ctx.trace, MODULES)
    work = ctx.work.get("multi_agg")
    if not t or not work or not work[0]:
        return None
    return roofline.share_pct(work[0], work[1], t, ctx.device_kind)
