"""Mean bytes sent to the device per product on the gather path (counter
``h2d_bytes``: value arrays and padded index arrays), in MB."""
from bench import gatherread


def read(ctx):
    return gatherread.counter(ctx, "h2d_bytes", 1e-6)
