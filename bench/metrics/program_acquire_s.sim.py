"""Seconds the set-up grid spent acquiring its compiled program
(``compile_s`` of ``repro.obs.telemetry.timed_compiled``): a disk
restore when the checkout's cache holds it, a compile otherwise."""


def read(ctx):
    warm = ctx.get("warm")
    return None if warm is None else warm["compile_s"]
