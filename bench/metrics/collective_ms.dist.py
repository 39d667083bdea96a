"""collective_ms.dist: device milliseconds per step per chip in the
cross-chip collective ops (`yardstick/ici.py` names them: the
instructions `reduce-scatter`, `all-reduce`, `all-gather`,
`collective-permute`, `all-to-all`, synchronous or as a `-start` /
`-done` pair timed from start to done), averaged over the chips."""
from yardstick import ici


def read(ctx):
    steps = ctx.records.get("steps", 0)
    secs, _ = ici.collective_s_per_chip(ctx.trace)
    if not steps or not secs:
        return None
    return 1e3 * secs / steps
