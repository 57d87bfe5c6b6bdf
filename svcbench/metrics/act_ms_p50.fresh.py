"""Maintenance: median wall of the planner's act phase per epoch (obs
``act`` spans; the cleans inside it end in ``block_until_ready``)."""

from svcbench.e2e import span_p50_ms


def read(ctx):
    return span_p50_ms(ctx.spans, ["act"])
