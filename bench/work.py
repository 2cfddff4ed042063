"""The work a request asks of the model, counted from the traffic.

Counts are of the algorithm, not of the program: whatever implements it
is judged against the same numbers. Each architecture module
(``bench/architectures/<name>.py``) counts its own in ``token_work``;
what they share is here. Model FLOPs are the matrix products at every
processed position (the prompt, then one position per later token) plus
the unembedding at each position that yields a token, plus attention.
Attention at the position with index ``i`` (0-based) runs over the
``i + 1`` positions up to it: ``4 * heads * head_dim * (i + 1)`` FLOPs
per layer (scores and the weighted sum). Its bytes are what one pass must
move at least: per layer and per call, the keys and values of the whole
context up to the call's last position once, and the queries and outputs
of its positions once. A prompt is processed in chunks of ``chunk``
positions, one call each; every later token is a call of one position.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Work"]


@dataclasses.dataclass(frozen=True)
class Work:
    model_flops: float = 0.0
    attn_flops: float = 0.0
    attn_bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(
            self.model_flops + o.model_flops,
            self.attn_flops + o.attn_flops,
            self.attn_bytes + o.attn_bytes,
        )


def _attn_call(w, first: int, n: int) -> tuple[float, float]:
    """FLOPs and bytes of one full causal attention call over positions
    ``first .. first + n - 1``, all ``w.n_layers`` layers. ``w`` has
    ``n_layers``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``kv_bytes``
    and ``act_bytes``."""
    ctx_sum = (first + 1 + first + n) * n / 2  # sum of (i + 1) over the call
    flops = 4 * w.n_heads * w.head_dim * ctx_sum
    kv = 2 * (first + n) * w.n_kv_heads * w.head_dim * w.kv_bytes
    qo = 2 * n * w.n_heads * w.head_dim * w.act_bytes
    return w.n_layers * flops, w.n_layers * (kv + qo)
