"""Device time of the all-reduce's ``reduce_scatter`` phase per call, in ms,
averaged over the chips: leaf-op time (``bench/scopereduce.py``) in
``jax.named_scope("reduce_scatter")`` (``core/collectives.py``) over the
calls the traced window completed.  A fusion counts in the scope of
its root instruction.
"""
from scopereduce import phase_ms


def read(ctx):
    return phase_ms(ctx, "reduce_scatter")
