"""Host seconds per window grid drawing an MoE layer's gate and building
its dispatch and combine counts: the program span ``sweep.gate``
(``repro.sim.workloads.moe_dispatch_workload``, inside ``sweep.traffic``)
on the profiler's host plane, over the window's grids."""
from scopereduce import host_s_per_grid


def read(ctx):
    return host_s_per_grid(ctx, ["sweep.gate"])
