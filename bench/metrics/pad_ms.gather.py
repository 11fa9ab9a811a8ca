"""Mean host time per product padding the gather plans' index arrays to
their bucketed lengths (``reap.values``), in milliseconds."""
from bench import gatherread


def read(ctx):
    return gatherread.span_ms(ctx, "reap.values")
