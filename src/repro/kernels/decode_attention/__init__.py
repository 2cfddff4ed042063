from .ops import decode_attention, decode_attention_ref
from .paged import (
    paged_attention,
    paged_decode_attention,
    paged_prefill_attention_pallas,
)
from .ref import (
    gather_pages,
    paged_decode_attention_ref,
    paged_prefill_attention,
    quantize_kv,
)

# The Pallas kernels whose traced computation must stay free of XLA
# pool gathers (the block-table walk lives in the BlockSpec index map).
# ``repro.analysis.entry_points`` traces each standalone so the
# zero-gather budget binds at the kernel boundary.
PALLAS_PAGED_KERNELS = {
    "paged_decode_attention": paged_decode_attention,
    "paged_prefill_attention": paged_prefill_attention_pallas,
}

__all__ = [
    "PALLAS_PAGED_KERNELS",
    "decode_attention",
    "decode_attention_ref",
    "paged_attention",
    "paged_decode_attention",
    "paged_decode_attention_ref",
    "paged_prefill_attention",
    "paged_prefill_attention_pallas",
    "gather_pages",
    "quantize_kv",
]
