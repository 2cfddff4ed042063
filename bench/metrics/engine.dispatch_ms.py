"""Host ms per engine step in its dispatch phase (``serving/engine.py``):
assembling and issuing every replica's stage calls. From the engine's
``dispatch`` mark to its ``commit`` mark, averaged over the window's steps."""

UNIT = "ms"
LAYER = "engine step loop"
MOVES = "itl_p50_ms"


def read(ctx):
    if not ctx.steps:
        return None
    return 1e3 * sum(s[2] - s[1] for s in ctx.steps) / len(ctx.steps)
