"""Device time of the grid program per simulated cycle, in ms.

The grid program is the program with the most device time in the
traced window (one launch per grid); its time is divided by the cycles
the window's grids simulated.  Cross-check: each grid's ``execute_s``.
"""


def read(ctx):
    s = ctx.get("summary")
    if s is None or not s.module_s or not ctx.get("cycles"):
        return None
    return max(s.module_s.values()) / ctx["cycles"] * 1e3
