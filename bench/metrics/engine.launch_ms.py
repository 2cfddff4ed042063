"""Host ms per stage call spent issuing its device work
(``serving/engine.py``): the time in the program's ``serve.launch`` spans
(the stage program's call, its argmax and the hand-offs sliced from its
output) over its ``serve.call`` spans, both as the profiler recorded them
in the traced part of the window, which the engine counts in
``ServerStats.traced_launch_s`` and ``traced_calls``."""

UNIT = "ms"
LAYER = "engine step loop"
MOVES = "itl_p50_ms"


def read(ctx):
    launch, calls = ctx.stats.get("traced_launch_s"), ctx.stats.get("traced_calls")
    if launch is None or not calls:
        return None
    return 1e3 * launch / calls
