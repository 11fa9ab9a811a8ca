"""How far the gather path's largest chunk runs over an even share of a
product's partial products: 100·(max chunk products · chunks / products −
1), from the counters ``gather_chunk_products_max`` and
``gather_products`` and the chunks launched (``reap.launch`` calls, one
per chunk).  None where the program keeps no such counter."""
from bench import gatherread, spanread


def read(ctx):
    largest = gatherread.counter(ctx, "gather_chunk_products_max")
    products = gatherread.counter(ctx, "gather_products")
    recs = spanread.window(ctx, gatherread.OP)
    if largest is None or not products or recs is None:
        return None
    chunks = sum(r.calls.get("reap.launch", 0) for r in recs) / len(recs)
    return 100.0 * (largest * chunks / products - 1.0)
