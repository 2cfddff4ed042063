"""Share of the traced interval in which no operation ran on the device:
1 - (union of the op intervals on the trace's ``XLA Ops`` line) / interval,
averaged over the chips used."""

UNIT = "%"
LAYER = "device"
MOVES = "tokens_per_s"


def read(ctx):
    if ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
