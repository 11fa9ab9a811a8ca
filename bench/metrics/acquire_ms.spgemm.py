"""Mean plan acquisition per product in ``ReapRuntime.run`` (route, pattern
digests, cache lookup: the ``reap.acquire`` span), in milliseconds."""
from bench import spanread


def read(ctx):
    return spanread.span_ms(ctx, "spgemm_block", "reap.acquire")
