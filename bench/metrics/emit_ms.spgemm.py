"""Mean host emit stage per product (value scatter into tiles), from the
``inspect_s`` key of the program's ``RunStats.extra`` on the chunked block
path, in milliseconds."""


def read(ctx):
    emit = ctx.counters.get("emit_s")
    return None if not emit else 1000.0 * sum(emit) / len(emit)
