"""Host ms per engine step blocked on the device's results
(``serving/engine.py``): the time in the program's ``serve.readback``
spans over its ``serve.step`` spans, both as the profiler recorded them
in the traced part of the window, which the engine counts in
``ServerStats.traced_readback_s`` and ``traced_steps``."""

UNIT = "ms"
LAYER = "engine step loop"
MOVES = "itl_p50_ms"


def read(ctx):
    wait, steps = ctx.stats.get("traced_readback_s"), ctx.stats.get("traced_steps")
    if wait is None or not steps:
        return None
    return 1e3 * wait / steps
