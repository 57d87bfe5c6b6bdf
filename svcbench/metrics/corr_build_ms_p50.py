"""Estimation: median wall of the correspondence rebuild on the first
batch of a view after its clean (obs ``corr_build`` spans, which end at
device completion when traced)."""

from svcbench.e2e import span_p50_ms


def read(ctx):
    return span_p50_ms(ctx.spans, ["corr_build"])
