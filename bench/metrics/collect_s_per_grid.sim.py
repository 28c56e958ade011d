"""Host seconds per window grid collecting the results: the program
spans ``sweep.fetch`` (the outputs back to the host), ``sweep.stats``
(the delivery-log rebuild, ``build_stats`` and ``attach_*``) and
``study.records`` (``Result.from_stats``), on the profiler's host
plane, over the window's grids."""
from scopereduce import host_s_per_grid


def read(ctx):
    return host_s_per_grid(ctx, ["sweep.fetch", "sweep.stats",
                                 "study.records"])
