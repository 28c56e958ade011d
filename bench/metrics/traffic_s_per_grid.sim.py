"""Host seconds per window grid generating traffic: the program span
``sweep.traffic`` (the traffic factory calls in ``xengine.sweep``), on
the profiler's host plane, over the window's grids."""
from scopereduce import host_s_per_grid


def read(ctx):
    return host_s_per_grid(ctx, ["sweep.traffic"])
