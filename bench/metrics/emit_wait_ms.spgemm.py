"""Mean time per product the caller waits on the emit worker
(``reap.emit_wait``), in milliseconds."""
from bench import spanread


def read(ctx):
    return spanread.span_ms(ctx, "spgemm_block", "reap.emit_wait")
