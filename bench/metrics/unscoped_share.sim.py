"""Share of the grid program's leaf-op device time that falls in no
stage of the cycle step, in % (``bench/scopereduce.py``): loop control,
copies of the loop state, and whatever a fusion's root leaves unnamed.
"""
from scopereduce import unscoped_share


def read(ctx):
    return unscoped_share(ctx)
