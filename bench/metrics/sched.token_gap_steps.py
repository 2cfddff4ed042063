"""Engine steps between consecutive tokens of a request
(``serving/engine.py``, ``scheduler.py``): the growth of
``ServerStats.token_gap_steps`` over that of ``token_gaps``, over the
window and its drain. Times the step length, it gives the token gap."""

UNIT = "steps"
LAYER = "router and scheduler"
MOVES = "itl_p50_ms"


def read(ctx):
    steps, n = ctx.stats.get("token_gap_steps"), ctx.stats.get("token_gaps")
    if steps is None or not n:
        return None
    return steps / n
