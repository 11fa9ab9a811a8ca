"""Per-product means of the gather executor's run records, for the readers
of the gather path's metrics.

As ``bench.spanread`` (op ``spgemm_gather``), and None besides wherever a
record of the window lacks the span or counter read: a program whose
gather path keeps none reports nothing, not zero.
"""
from __future__ import annotations

from bench import spanread

OP = "spgemm_gather"


def span_ms(ctx, *names: str):
    """Mean milliseconds per product in the spans ``names`` together."""
    recs = spanread.window(ctx, OP)
    if recs is None or any(n not in r.seconds for r in recs for n in names):
        return None
    return spanread.span_ms(ctx, OP, *names)


def counter(ctx, name: str, scale: float = 1.0):
    """Mean of counter ``name`` per product, times ``scale``."""
    recs = spanread.window(ctx, OP)
    if recs is None or any(name not in r.counters for r in recs):
        return None
    return spanread.counter(ctx, OP, name, scale)
