"""The work a request asks of the model, counted from the traffic.

Counts are of the algorithm, not of the program: whatever implements it
is judged against the same numbers. Model FLOPs are the matrix products
at every processed position (the prompt, then one position per later
token) plus the unembedding at each position that yields a token, plus
attention. Attention at the position with index ``i`` (0-based) runs over
the ``i + 1`` positions up to it: ``4 * heads * head_dim * (i + 1)`` FLOPs
per layer (scores and the weighted sum). Its bytes are what one pass must
move at least: per layer and per call, the keys and values of the whole
context up to the call's last position once, and the queries and outputs
of its positions once. A prompt is processed in chunks of ``chunk``
positions, one call each; every later token is a call of one position.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Widths", "Work", "token_work", "request_work"]


@dataclasses.dataclass(frozen=True)
class Widths:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 2  # bytes per cached key/value element (bf16)
    act_bytes: int = 2  # bytes per query/output element (bf16)

    @classmethod
    def from_config(cls, c: dict) -> "Widths":
        return cls(
            n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["intermediate_size"],
            vocab=c["vocab_size"],
        )

    @property
    def matmul_flops_per_position(self) -> int:
        """Projections and SwiGLU of one position through every layer."""
        D, H, KV, Dh, F = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.d_ff
        per_layer = 2 * (D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F)
        return self.n_layers * per_layer


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


def _attn_call(w: Widths, first: int, n: int) -> tuple[float, float]:
    """FLOPs and bytes of one attention call over positions
    ``first .. first + n - 1``, all layers."""
    ctx_sum = (first + 1 + first + n) * n / 2  # sum of (i + 1) over the call
    flops = 4 * w.n_heads * w.head_dim * ctx_sum
    kv = 2 * (first + n) * w.n_kv_heads * w.head_dim * w.kv_bytes
    qo = 2 * n * w.n_heads * w.head_dim * w.act_bytes
    return w.n_layers * flops, w.n_layers * (kv + qo)


def token_work(w: Widths, prompt_len: int, k: int, chunk: int) -> Work:
    """The work that delivers output token ``k`` (0-based) of a request:
    the whole chunked prefill for ``k == 0``, one decode position after."""
    if k == 0:
        af = ab = 0.0
        for start in range(0, prompt_len, chunk):
            f, b = _attn_call(w, start, min(chunk, prompt_len - start))
            af, ab = af + f, ab + b
        positions = prompt_len
    else:
        af, ab = _attn_call(w, prompt_len + k - 1, 1)
        positions = 1
    model = positions * w.matmul_flops_per_position + 2 * w.d_model * w.vocab + af
    return Work(model, af, ab)


def request_work(w: Widths, prompt_len: int, n_out: int, chunk: int) -> Work:
    total = Work()
    for k in range(n_out):
        total = total + token_work(w, prompt_len, k, chunk)
    return total
