"""Pure-jnp oracles for flash-decode and paged flash-decode, plus the
scatter-time int8 page quantizer shared by models and engine."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-row int8 quantization of K/V cache entries.

    ``x[..., KV, D]`` -> (int8 values, fp32 scales ``[...]``): one amax
    scale per token row (all KV heads x head_dim of one cache entry).
    Scales live per page *row*, not one scalar per page, deliberately:
    pages fill incrementally (decode writes one row per step), and a
    whole-page amax would force requantizing every previously written
    row on each scatter. All-zero rows get scale 1 so dequant stays 0.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None, None]), -127, 127)
    return q.astype(jnp.int8), scale


def dot_rows(c: int, on_cpu: bool) -> int:
    """Query rows the attention dots run with for ``c`` queries.

    XLA:CPU takes a matrix-vector path for a one-row dot whose sum order
    differs from the matrix-matrix one, so there a single row is padded
    to two: a decode row is then bit-identical to the same row of a
    speculative verify chunk, which the engine's exactness rests on.
    Other backends run ``c`` rows.
    """
    return max(c, 2) if on_cpu else c


def page_rows(pages: jnp.ndarray, scales: jnp.ndarray | None, layer):
    """A pool as a stack of page rows ``[L, P, page, KV * D]``, its
    scales ``[L, P, page]`` and the layer to read.

    With ``layer``, ``pages`` is a layer stack ``[L, P, page, ...]``;
    without, one layer's pool ``[P, page, ...]``, read as a stack of one
    at layer 0. A row is the ``KV * D`` values of one position, heads
    side by side, kept as ``[KV, D]`` or as one axis: the same bytes in
    row-major order, and no op at all for a pool kept as rows.
    """
    if layer is None:
        pages = pages.reshape((1,) + pages.shape[:2] + (-1,))
        scales = None if scales is None else scales[None]
        return pages, scales, 0
    return pages.reshape(pages.shape[:3] + (-1,)), scales, layer


def gather_pages(
    pages: jnp.ndarray,
    block_tables: jnp.ndarray,
    scales: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,
    head_dim: int | None = None,
) -> jnp.ndarray:
    """Materialize a contiguous cache from a page pool.

    pages: one layer's pool [P, page, *row] or, with ``layer``, a layer
    stack [L, P, page, *row] gathered at that layer straight from the
    stack; block_tables: [B, NB] -> [B, NB*page, *row], where a row is
    [KV, D] or [KV * D], and with ``head_dim`` [B, NB*page, KV, D]. With
    ``scales`` ([P, page] or [L, P, page] per-row fp32, int8 pools) the
    gathered rows are dequantized: ``pages[bt] * scales[bt]``.
    """
    at = (block_tables,) if layer is None else (layer, block_tables)
    out = pages[at]  # [B, NB, page, *row]
    B, NB, page = out.shape[:3]
    row = out.shape[3:] if head_dim is None else (-1, head_dim)
    out = out.reshape((B, NB * page) + row)
    if scales is None:
        return out
    s = scales[at].reshape(B, NB * page)
    return out.astype(s.dtype) * s.reshape(s.shape + (1,) * (out.ndim - 2))


def paged_decode_attention_ref(
    q: jnp.ndarray,  # [B, 1, H, D] (model layout)
    k_pages: jnp.ndarray,  # one layer's [P, page, KV, D]; with layer, [L, P, page, ...]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, NB] int32
    lengths: jnp.ndarray,  # [B] int32, valid entries incl. current token
    *,
    layer: jnp.ndarray | None = None,
    window: int | None = None,
    k_scales: jnp.ndarray | None = None,  # [(L,) P, page] fp32 (int8 pools)
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Gather-then-attend oracle for the paged kernel. Returns [B,1,H,D]."""
    B, _, H, D = q.shape
    k = gather_pages(k_pages, block_tables, k_scales, layer, D)  # [B, S, KV, D]
    v = gather_pages(v_pages, block_tables, v_scales, layer, D)
    return decode_attention_ref(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,)),
        window=window,
    ).transpose(0, 2, 1, 3)


def paged_prefill_attention(
    q: jnp.ndarray,  # [B, C, H, D] (model layout) — C new tokens per lane
    k_pages: jnp.ndarray,  # one layer's [P, page, KV, D]; with layer, [L, P, page, ...]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, NB] int32
    offsets: jnp.ndarray,  # [B] int32 absolute position of q[:, 0]
    *,
    layer: jnp.ndarray | None = None,
    k_scales: jnp.ndarray | None = None,  # [(L,) P, page] fp32 (int8 pools)
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Prefill-over-paged-prefix attention — the gather fallback.

    Chunked prefill writes each chunk's K/V into the request's reserved
    pages and then needs the chunk's queries to attend causally over the
    whole paged prefix. This fallback materializes each lane's pages
    (one gather, dequantized for int8 pools) and runs masked attention;
    the Pallas kernel that walks the block table directly —
    :func:`.paged.paged_prefill_attention_pallas`, the
    multi-query sibling of :func:`.paged.paged_decode_attention` —
    replaces it behind this signature on TPU, and this fallback stays as
    the off-TPU path and test oracle. Query ``i`` of lane ``b`` attends
    positions ``<= offsets[b] + i``; rows past the caller's valid count
    produce garbage that the engine discards. The lanes' pages are read
    from layer ``layer`` of the pool stack, as the kernel reads them.
    Returns [B, C, H, D].
    """
    B, C_out, H, D = q.shape
    k = gather_pages(k_pages, block_tables, k_scales, layer, D)  # [B, S, KV, D]
    v = gather_pages(v_pages, block_tables, v_scales, layer, D)
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = D**-0.5
    C = dot_rows(C_out, jax.default_backend() == "cpu")
    q = jnp.pad(q, ((0, 0), (0, C - C_out), (0, 0), (0, 0)))
    qg = q.astype(jnp.float32).reshape(B, C, KV, G, D) * scale
    s = jnp.einsum("bckgd,bskd->bckgs", qg, k.astype(jnp.float32))
    q_pos = offsets[:, None] + jnp.arange(C, dtype=jnp.int32)  # [B, C]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    mask = kv_pos[None, None, :] <= q_pos[:, :, None]  # causal incl. self
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bckgs,bskd->bckgd", p, v.astype(jnp.float32))
    return out.reshape(B, C, H, D)[:, :C_out].astype(q.dtype)


def decode_attention_ref(
    q: jnp.ndarray,  # [B, H, 1, D]
    k_cache: jnp.ndarray,  # [B, KV, S, D]
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,  # [B]
    *,
    window: int | None = None,
) -> jnp.ndarray:
    B, H, _, D = q.shape
    _, KV, S, _ = k_cache.shape
    G = H // KV
    scale = D**-0.5

    qg = q.astype(jnp.float32).reshape(B, KV, G, D) * scale
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache.astype(jnp.float32))
    pos = jnp.arange(S)[None, :]
    mask = pos < lengths[:, None]
    if window is not None:
        mask = mask & (pos >= lengths[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, 1, D).astype(q.dtype)
