"""Least time of one matvec's work at the chip's peaks, over the device
busy time per matvec (one per CG iteration).  Work is counted from the
pattern (``bench.ops.cg.work``)."""


def read(ctx):
    if ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    matvecs = sum(ctx.counters["iterations"])
    if matvecs == 0:
        return None
    w = ctx.work()
    least = max(w["flops"] / ctx.peaks["flops_per_s"],
                w["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.trace["busy_s"] / matvecs)
