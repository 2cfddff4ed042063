"""Engine steps from a request's submission to the step in which its
first token lands (``serving/engine.py``, ``scheduler.py``): the growth
of ``ServerStats.first_token_steps`` over that of ``first_tokens``, over
the window and its drain. Times the step length, it gives the time to
the first token."""

UNIT = "steps"
LAYER = "router and scheduler"
MOVES = "ttft_p50_s"


def read(ctx):
    steps, n = ctx.stats.get("first_token_steps"), ctx.stats.get("first_tokens")
    if steps is None or not n:
        return None
    return steps / n
