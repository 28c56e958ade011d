"""Device time of the cycle step's ``arbitrate`` stage per simulated
cycle, in ms: the credit check and link arbitration
(``jax.named_scope("arbitrate")`` in ``xengine._step``).

Leaf-op time (``bench/scopereduce.py``) of the grid program that
``step_ms.sim`` picks, in that scope, over the window's simulated
cycles. A fusion counts in the scope of its root instruction.
"""
from scopereduce import stage_ms


def read(ctx):
    return stage_ms(ctx, "arbitrate")
