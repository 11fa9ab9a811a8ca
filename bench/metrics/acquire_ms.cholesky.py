"""Mean plan acquisition per factor in ``ReapRuntime.run`` (pattern digest,
cache lookup: the ``reap.acquire`` span), in milliseconds."""
from bench import spanread


def read(ctx):
    return spanread.span_ms(ctx, "cholesky", "reap.acquire")
