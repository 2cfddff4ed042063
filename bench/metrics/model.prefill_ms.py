"""Device ms per call of a stage's chunked-prefill program
(``models/transformer.py:prefill_chunk_paged`` under the engine's
``chunk_pages``), from its events on the trace's ``XLA Modules`` line."""

UNIT = "ms"
LAYER = "model step"
MOVES = "ttft_p50_s"
MODULE = r"^jit_chunk_pages\b"


def read(ctx):
    evs = ctx.trace.module_events(MODULE)
    if not evs:
        return None
    return 1e3 * sum(e - s for _, s, e in evs) / len(evs)
