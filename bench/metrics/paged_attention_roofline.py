"""Share of its roofline the paged attention kernel reaches
(``kernels/decode_attention/paged.py``): the least time the chip needs for
the attention work the clients received in the traced interval (the
larger of its FLOPs over the peak FLOP/s and its bytes over the peak
bandwidth, as the architecture module's ``token_work`` counts them), over
the kernel's device time there."""

UNIT = "%"
LAYER = "attention kernel"
MOVES = "itl_p50_ms"
KERNEL = r"^paged_attention$"


def read(ctx):
    t = sum(e - s for _, s, e in ctx.trace.op_events(KERNEL))
    if t <= 0 or ctx.work.attn_flops <= 0 or ctx.peaks is None:
        return None
    least = max(ctx.work.attn_flops / ctx.peaks.flops_bf16, ctx.work.attn_bytes / ctx.peaks.hbm_bw)
    return 100.0 * least / t
