"""Least time of one product's work at the chip's peaks, over the device
busy time per product, on the gather/VPU executor.  Work is counted from
the pattern (``bench.ops.spgemm.work``), not by the program."""


def read(ctx):
    if ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    w = ctx.work()
    least = max(w["flops"] / ctx.peaks["flops_per_s"],
                w["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.trace["busy_s"] / ctx.n_ops)
