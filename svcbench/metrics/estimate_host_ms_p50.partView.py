"""Estimation: median host time of a partView dashboard batch's ``estimate``
span — its wall less its ``fetch`` spans (device-to-host reads, which
wait for the device) and its ``corr_build`` subtree (the correspondence
rebuild after a clean, read by ``corr_build_ms_p50``)."""

from svcbench.spans import p50_ms, walls_less


def read(ctx):
    return p50_ms(walls_less(ctx.spans, "estimate", ("fetch", "corr_build"),
                             view="partView"))
