"""Requests preempted by the KV page manager (``serving/cache.py``) per
request attempted: the growth of the engine's ``preempted_jobs`` over the
window, over the attempted requests."""

UNIT = "preempt/req"
LAYER = "KV cache manager"
MOVES = "ttft_p50_s"


def read(ctx):
    if ctx.attempted <= 0:
        return None
    return ctx.stats["preempted_jobs"] / ctx.attempted
