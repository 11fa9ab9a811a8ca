"""Mean time per product turning output tiles into the CSR result
(``reap.extract``: concatenation and ``block_result_to_csr``), in
milliseconds."""
from bench import spanread


def read(ctx):
    return spanread.span_ms(ctx, "spgemm_block", "reap.extract")
