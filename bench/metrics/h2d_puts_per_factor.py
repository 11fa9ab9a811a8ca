"""Host-to-device transfers per factor (counter ``h2d_puts``: the value
array, then six index arrays per etree level)."""
from bench import spanread


def read(ctx):
    return spanread.counter(ctx, "cholesky", "h2d_puts")
