"""Share of the index slots sent to the gather executor that carry no
partial product: 100·(slots − products)/slots, from the counters
``gather_slots`` and ``gather_products``."""
from bench import gatherread


def read(ctx):
    products = gatherread.counter(ctx, "gather_products")
    slots = gatherread.counter(ctx, "gather_slots")
    if products is None or not slots:
        return None
    return 100.0 * (slots - products) / slots
