"""Device program executions per factorization, from the trace."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["launches"]:
        return None
    return ctx.trace["launches"] / ctx.n_ops
