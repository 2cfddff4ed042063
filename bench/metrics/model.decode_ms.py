"""Device ms per call of a stage's paged decode program
(``models/transformer.py:decode_step_paged`` under the engine's
``decode_fn``), from its events on the trace's ``XLA Modules`` line."""

UNIT = "ms"
LAYER = "model step"
MOVES = "itl_p50_ms"
MODULE = r"^jit_decode_fn\b"


def read(ctx):
    evs = ctx.trace.module_events(MODULE)
    if not evs:
        return None
    return 1e3 * sum(e - s for _, s, e in evs) / len(evs)
