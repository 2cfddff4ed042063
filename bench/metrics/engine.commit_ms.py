"""Host ms per engine step in its commit phase (``serving/engine.py``),
the waits on the device's results included. From the engine's ``commit``
mark to the mark that ends it, averaged over the window's steps."""

UNIT = "ms"
LAYER = "engine step loop"
MOVES = "itl_p50_ms"


def read(ctx):
    if not ctx.steps:
        return None
    return 1e3 * sum(s[3] - s[2] for s in ctx.steps) / len(ctx.steps)
