"""Mean host time per product in transfers on the gather path: values and
padded index arrays to the device (``reap.h2d``) and each chunk's result
back, with the wait for the device (``reap.fetch``), in milliseconds."""
from bench import gatherread


def read(ctx):
    return gatherread.span_ms(ctx, "reap.h2d", "reap.fetch")
