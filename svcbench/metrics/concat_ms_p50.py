"""Ingest: median wall of a pending-delta merge (obs ``concat`` spans:
segments pulled to the host, sorted and uploaded as one arena)."""

from svcbench.e2e import span_p50_ms


def read(ctx):
    return span_p50_ms(ctx.spans, ["concat"])
