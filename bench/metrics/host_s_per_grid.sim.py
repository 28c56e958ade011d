"""Host seconds per window grid outside the compiled program: the
grid's wall time through ``Study.run`` less the program's
``execute_s`` (``repro.obs.telemetry.timed_compiled``), mean over the
window's grids.  Covers traffic generation, packing, transfer and the
statistics rebuild.
"""


def read(ctx):
    grids = ctx.get("grids")
    if not grids:
        return None
    return sum(g["wall_s"] - g["execute_s"] for g in grids) / len(grids)
