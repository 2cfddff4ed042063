"""Model FLOP utilization of the stage programs: the model FLOPs of the
work the clients received in the traced interval (:mod:`bench.work`),
over the device time of the decode and chunked-prefill programs there
times the chip's peak bf16 FLOP/s."""

UNIT = "%"
LAYER = "model step"
MOVES = "itl_p50_ms"
MODULES = r"^jit_(decode_fn|chunk_pages)\b"


def read(ctx):
    t = sum(e - s for _, s, e in ctx.trace.module_events(MODULES))
    if t <= 0 or ctx.work.model_flops <= 0 or ctx.peaks is None:
        return None
    return 100.0 * ctx.work.model_flops / (t * ctx.peaks.flops_bf16)
