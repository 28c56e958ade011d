"""Host seconds per window grid preparing the program's inputs: the
program spans ``study.resolve`` (topology and traffic factory),
``sweep.pack`` (``_pack_traffic``, the flat concatenation and padding),
``sweep.tables`` (link and index tables, their digest) and
``sweep.transfer`` (the flat arrays, key and warmups to the device), on
the profiler's host plane, over the window's grids."""
from scopereduce import host_s_per_grid


def read(ctx):
    return host_s_per_grid(ctx, ["study.resolve", "sweep.pack",
                                 "sweep.tables", "sweep.transfer"])
