"""Mean host time per product in transfers: operand tiles and schedules to
the device (``reap.h2d``) and output tiles back, with the wait for the
kernel (``reap.fetch``), in milliseconds."""
from bench import spanread


def read(ctx):
    return spanread.span_ms(ctx, "spgemm_block", "reap.h2d", "reap.fetch")
