"""collective_roofline.dist: percent of the chip's published ICI
bandwidth (`yardstick/ici.py`) that the collective reaches: the least
bytes one chip must send in the window's collectives, as the program
counts them from static shapes (`repro_distributed_collective_bytes_total`;
a reduce-scatter's (p-1)/p of the accumulator, where v5e runs an
all-reduce that sends twice that), over the collective ops' device
seconds per chip in the trace, over the bandwidth.  No reading where a
collective's start has no done in the window: its time would be cut
short."""
from yardstick import ici

BYTES = "repro_distributed_collective_bytes_total"


def read(ctx):
    sent = ctx.delta.counter(BYTES)
    bw = ici.ici_bw(ctx.peaks)
    secs, closed = ici.collective_s_per_chip(ctx.trace)
    if not sent or bw is None or not secs or not closed:
        return None
    return 100.0 * sent / secs / bw
