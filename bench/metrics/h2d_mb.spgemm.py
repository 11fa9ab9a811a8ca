"""Mean bytes sent to the device per product (counter ``h2d_bytes``: operand
tiles and schedule arrays), in MB."""
from bench import spanread


def read(ctx):
    return spanread.counter(ctx, "spgemm_block", "h2d_bytes", 1e-6)
