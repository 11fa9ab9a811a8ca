"""Mean bytes sent to the device per factor (counter ``h2d_bytes``: the value
array and every level's index bundle), in MB."""
from bench import spanread


def read(ctx):
    return spanread.counter(ctx, "cholesky", "h2d_bytes", 1e-6)
