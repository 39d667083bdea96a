"""slot_fill: percent of the contribution slots that the device ran over
in the window (padding included, `repro_kernel_slots_total`) that held a
real contribution (`repro_kernel_contributions_total`).  The program
counts both from its plan's static shapes; nothing is read from the
device."""

SLOTS = "repro_kernel_slots_total"
CONTRIBUTIONS = "repro_kernel_contributions_total"


def read(ctx):
    slots = ctx.delta.counter(SLOTS)
    if not slots:
        return None
    return 100.0 * ctx.delta.counter(CONTRIBUTIONS) / slots
