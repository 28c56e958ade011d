"""The all-reduce's share of its roofline, in %.

An all-reduce of B bytes over n chips must move at least
2 (n - 1) / n * B bytes out of each chip; at the chip's interconnect
bandwidth (``bench/peaks.json``) that is its least time.  The share is
that over the device time per call: the traced window's busy time over
the calls it completed.
"""


def read(ctx):
    s = ctx.get("summary")
    if s is None or not ctx.get("calls"):
        return None
    n = ctx["chips"]
    least_s = (2 * (n - 1) / n * ctx["bucket_bytes"]
               / ctx["peaks"]["ici_bytes_per_s"])
    return 100.0 * least_s / (s.busy_s / ctx["calls"])
