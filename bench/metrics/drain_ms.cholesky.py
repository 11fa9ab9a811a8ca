"""Mean wait per factor for the device to finish after the last level is
dispatched (``reap.drain``), in milliseconds."""
from bench import spanread


def read(ctx):
    return spanread.span_ms(ctx, "cholesky", "reap.drain")
