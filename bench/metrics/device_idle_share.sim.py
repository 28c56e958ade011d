"""Share of the traced window in which no operation ran on the
device, in % (1 - busy / window, ``bench/tracereduce.py``)."""


def read(ctx):
    s = ctx.get("summary")
    if s is None or ctx.get("grids") is None:
        return None
    return 100.0 * s.idle_share
