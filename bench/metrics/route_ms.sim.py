"""Device time of the cycle step's ``route`` stage per simulated cycle,
in ms: transit requests, injection candidates and the policy itinerary
(``jax.named_scope("route")`` in ``xengine._step``).

Leaf-op time (``bench/scopereduce.py``) of the grid program that
``step_ms.sim`` picks, in that scope, over the window's simulated
cycles. A fusion counts in the scope of its root instruction.
"""
from scopereduce import stage_ms


def read(ctx):
    return stage_ms(ctx, "route")
