"""useful_fill: percent of the contribution slots that the device ran
over in the window (`repro_kernel_slots_total`) that held a contribution
whose donor's label was known (`repro_kernel_contributions_total`,
donor="labeled"): the only ones that add to Z."""

SLOTS = "repro_kernel_slots_total"
CONTRIBUTIONS = "repro_kernel_contributions_total"


def read(ctx):
    slots = ctx.delta.counter(SLOTS)
    if not slots:
        return None
    return 100.0 * ctx.delta.counter(CONTRIBUTIONS, donor="labeled") / slots
