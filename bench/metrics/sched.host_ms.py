"""Host ms per engine step before its dispatch phase: harvest, aborts of
dead replicas' calls, re-placement and admission (``serving/scheduler.py``,
``router.py``, ``budget.py``). From the step's entry to the engine's
``dispatch`` mark, averaged over the window's steps."""

UNIT = "ms"
LAYER = "router and scheduler"
MOVES = "ttft_p50_s"


def read(ctx):
    if not ctx.steps:
        return None
    return 1e3 * sum(s[1] - s[0] for s in ctx.steps) / len(ctx.steps)
