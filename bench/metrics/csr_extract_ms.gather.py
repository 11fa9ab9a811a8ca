"""Mean time per product stitching the gather chunks' results into one CSR
(``reap.extract``), in milliseconds."""
from bench import gatherread


def read(ctx):
    return gatherread.span_ms(ctx, "reap.extract")
