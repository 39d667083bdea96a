"""device_idle.batch: percent of the traced window in which no op ran on
the device (1 - busy / window, busy = union of op intervals)."""
from yardstick import trace


def read(ctx):
    if not ctx.trace["ops"]:
        return None
    return 100.0 * trace.idle_share(ctx.trace)
