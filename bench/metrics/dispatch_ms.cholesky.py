"""Mean host time per factor dispatching the level steps (``reap.dispatch``:
one launch and its index-bundle transfers per etree level), in
milliseconds."""
from bench import spanread


def read(ctx):
    return spanread.span_ms(ctx, "cholesky", "reap.dispatch")
