"""The compiled cycle step's share of its roofline, in %.

The least time of a simulated cycle is the least state traffic of one
cycle (``bench/stepbytes.py``, from the fabric's shape) over the chip's
HBM bandwidth (``bench/peaks.json``); the share is that over the
measured device time per cycle (``step_ms.sim``).  The step does no
floating-point work to speak of, so bandwidth bounds it.
"""


def read(ctx):
    s = ctx.get("summary")
    if s is None or not s.module_s or not ctx.get("cycles"):
        return None
    per_cycle_s = max(s.module_s.values()) / ctx["cycles"]
    least_s = ctx["step_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / per_cycle_s
