"""Device time of the cycle step's ``rng`` stage per simulated cycle, in
ms: the measurement-window mask and the per-lane random words
(threefry) (``jax.named_scope("rng")`` in ``xengine._step``).

Leaf-op time (``bench/scopereduce.py``) of the grid program that
``step_ms.sim`` picks, in that scope, over the window's simulated
cycles. A fusion counts in the scope of its root instruction.
"""
from scopereduce import stage_ms


def read(ctx):
    return stage_ms(ctx, "rng")
