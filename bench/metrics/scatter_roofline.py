"""scatter_roofline: percent of the chip's roofline that the GEE scatter
kernel reaches.  The least time for the algorithm's work (from n, s and
K alone, `counts.scatter_work`) over the summed device time of the
kernel's events in the trace; one event is one pass over the graph."""
from yardstick import counts, trace

KERNEL = "gee_scatter_pallas"


def read(ctx):
    ev = trace.kernel_events(ctx.trace, KERNEL)
    if not ev or ctx.peaks is None:
        return None
    r = ctx.records
    b, f = counts.scatter_work(r["n"], r["s"], r["K"])
    secs = sum(e - s for s, e in ev) / 1e9
    return counts.roofline_share(b * len(ev), f * len(ev), secs, ctx.peaks)
