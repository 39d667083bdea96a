"""window_compiles: backend compiles inside the window, from the
program's `repro_jax_compiles_total`.  A warmed-up window reads 0; no
reading where the program does not count compiles."""

COMPILES = "repro_jax_compiles_total"


def read(ctx):
    after = ctx.delta.after.get("counters", {})
    if not any(s.partition("{")[0] == COMPILES for s in after):
        return None
    return ctx.delta.counter(COMPILES)
