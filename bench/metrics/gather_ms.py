"""gather_ms: device milliseconds per step spent in the step's device ops
other than the scatter kernel (the label and weight gathers, `where`,
multiply, slice), from the trace."""
from yardstick import trace

KERNEL = "gee_scatter_pallas"


def read(ctx):
    steps = ctx.records.get("steps", 0)
    if not steps or not trace.kernel_events(ctx.trace, KERNEL):
        return None
    other = trace.ops_s(ctx.trace) - trace.kernel_s(ctx.trace, KERNEL)
    return 1e3 * other / steps
