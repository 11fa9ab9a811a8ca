"""Per-op means of the program's run records, for the metric readers that
read spans and counters.

``ReapRuntime.run`` keeps one record per call (``repro.runtime.spans``):
seconds per span name and counters.  A reader takes the last ``n_ops``
records: nothing calls the runtime between the window's last op and the
readers, so they are the window's operations.  Every function here returns
None, and never raises, when the program keeps no records (a checkout
without ``repro/runtime/spans.py``), when fewer than ``n_ops`` records
exist, or when a record is of another op than the cell's.
"""
from __future__ import annotations


def window(ctx, op: str):
    """The window's records, or None."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    n = int(ctx.n_ops)
    recs = spans.recent(n) if n > 0 else []
    if not recs or len(recs) < n \
            or any(getattr(r, "op", None) != op for r in recs):
        return None
    return recs


def span_ms(ctx, op: str, *names: str):
    """Mean milliseconds per op in the spans ``names`` together."""
    recs = window(ctx, op)
    if recs is None:
        return None
    total = sum(r.seconds.get(name, 0.0) for r in recs for name in names)
    return 1000.0 * total / len(recs)


def counter(ctx, op: str, name: str, scale: float = 1.0):
    """Mean of counter ``name`` per op, times ``scale``."""
    recs = window(ctx, op)
    if recs is None:
        return None
    return scale * sum(r.counters.get(name, 0) for r in recs) / len(recs)
