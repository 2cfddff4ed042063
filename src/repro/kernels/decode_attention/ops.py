"""Dense flash-decode (model layout [B, 1, H, D]) on the paged kernel.

A contiguous per-request cache [B, S, KV, D] is a page pool whose pages
are ``chunk``-row slices of each request's cache: the reshape to
[B * S / chunk, chunk, KV, D] is free, and the block table of lane
``b`` is the identity run ``b * NC .. b * NC + NC - 1``. So dense decode
runs :func:`.paged.paged_attention` — per-lane lengths in scalar
prefetch, the softmax online across chunks — and there is one decode
kernel to keep.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged import paged_attention
from .ref import decode_attention_ref as _ref

__all__ = ["decode_attention", "decode_attention_ref"]


def decode_attention(
    q: jax.Array,  # [B, 1, H, D]
    k_cache: jax.Array,  # [B, S, KV, D]
    v_cache: jax.Array,
    lengths: jax.Array,  # [B] or scalar, valid entries incl. current token
    *,
    window: int | None = None,
    chunk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, S, KV, D = k_cache.shape
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    chunk = min(chunk, S)
    pad = -S % chunk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    NC = (S + pad) // chunk
    table = jnp.arange(B * NC, dtype=jnp.int32).reshape(B, NC)
    return paged_attention(
        q,
        k_cache.reshape(B * NC, chunk, KV, D),
        v_cache.reshape(B * NC, chunk, KV, D),
        table,
        lengths - 1,
        window=window,
        interpret=interpret,
    )


def decode_attention_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    window: int | None = None,
) -> jax.Array:
    B = q.shape[0]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    return _ref(
        q.transpose(0, 2, 1, 3),
        k_cache.transpose(0, 2, 1, 3),
        v_cache.transpose(0, 2, 1, 3),
        lengths,
        window=window,
    ).transpose(0, 2, 1, 3)
