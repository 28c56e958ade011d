"""Share of the traced window in which no operation ran on the
devices, in % (1 - busy / window averaged over the chips,
``bench/tracereduce.py``)."""


def read(ctx):
    s = ctx.get("summary")
    if s is None or ctx.get("calls") is None:
        return None
    return 100.0 * s.idle_share
