"""Attention: GQA with RoPE, chunked online-softmax (flash-style) XLA
path, sliding windows, and single-token decode against a KV cache.

The chunked path never materializes the full [S, S] score matrix: it
scans KV chunks carrying running (max, denom, accumulator) — the same
algorithm the Pallas kernel (:mod:`repro.kernels.flash_attention`)
implements with VMEM tiles, so it doubles as the kernel's oracle at the
model level.

Sliding windows are dynamic values (not static branches) so layer stacks
with mixed window/global layers (hymba) run under one ``lax.scan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import logical
from .common import ModelConfig, ParamSpec
from .layers import apply_rope, rmsnorm

__all__ = [
    "attn_template",
    "attention_block",
    "paged_attention_block",
    "chunk_attention_block",
    "paged_chunk_attention_block",
    "cross_attention_block",
    "project_kv",
    "chunked_attention",
    "decode_attention",
    "NEG_INF",
]

NEG_INF = -1e30


def attn_template(cfg: ModelConfig, n_layers: int | None = None) -> dict:
    L = n_layers if n_layers is not None else cfg.n_layers
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # Fan-in scales: the projections contract over D (q/k/v) and over
    # H x Dh (o), not over the second-to-last axis the default assumes.
    s_in, s_out = D**-0.5, (H * Dh) ** -0.5
    qkv_axes = ("layers", "embed_fsdp", "heads", "head_dim")
    kv_axes = ("layers", "embed_fsdp", "kv_heads", "head_dim")
    t = {
        "wq": ParamSpec((L, D, H, Dh), qkv_axes, scale=s_in),
        "wk": ParamSpec((L, D, KV, Dh), kv_axes, scale=s_in),
        "wv": ParamSpec((L, D, KV, Dh), kv_axes, scale=s_in),
        "wo": ParamSpec(
            (L, H, Dh, D), ("layers", "heads", "head_dim", "embed_fsdp"),
            scale=s_out,
        ),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamSpec((L, H, Dh), ("layers", "heads", "head_dim"), init="zeros")
        t["bk"] = ParamSpec((L, KV, Dh), ("layers", "kv_heads", "head_dim"), init="zeros")
        t["bv"] = ParamSpec((L, KV, Dh), ("layers", "kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        t["q_norm"] = ParamSpec((L, Dh), ("layers", "head_dim"), init="ones")
        t["k_norm"] = ParamSpec((L, Dh), ("layers", "head_dim"), init="ones")
    return t


def _project_qkv(x, p, cfg: ModelConfig, positions):
    """x [B,S,D] -> q [B,S,H,Dh], k/v [B,S,KV,Dh] with RoPE applied."""
    dtype = cfg.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: jax.Array | int | None = None,
    chunk: int = 1024,
    q_offset: jax.Array | int = 0,
    kv_stream: bool = False,
) -> jax.Array:
    """Online-softmax attention over KV chunks (no [S,S] materialization).

    q: [B,Sq,H,Dh]; k, v: [B,Skv,KV,Dh]; H = G * KV (GQA).
    ``window``: dynamic sliding-window size (None/huge = full attention).
    ``q_offset``: absolute position of q[0] (prefill continuation) — a
    scalar, or a per-batch [B] vector when each lane continues from its
    own offset (chunked prefill over a shared-width call).
    ``kv_stream``: slice K/V per chunk inside the scan (no stacked
    transposed copies of the whole K/V) and keep dot operands bf16 with
    fp32 accumulation — see EXPERIMENTS.md §Perf.
    Returns [B,Sq,H,Dh].
    """
    B, Sq, H, Dh = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = Dh**-0.5
    if window is None:
        window = jnp.int32(2**30)
    window = jnp.asarray(window, jnp.int32)
    q_offset = jnp.asarray(q_offset, jnp.int32)

    # [Sq] for a scalar offset, [B, Sq] for per-batch offsets.
    q_pos = q_offset[..., None] + jnp.arange(Sq, dtype=jnp.int32)
    if q_offset.ndim == 0:
        q_pos = q_pos.reshape(Sq)

    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    if kv_stream:
        qg = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(B, Sq, KV, G, Dh)
    else:
        qg = q.reshape(B, Sq, KV, G, Dh).astype(jnp.float32) * scale
        # [C, B, chunk, KV, Dh] chunks as scan inputs (baseline: one
        # transposed copy of K and V).
        kc = k.reshape(B, n_chunks, chunk, KV, Dh).transpose(1, 0, 2, 3, 4)
        vc = v.reshape(B, n_chunks, chunk, KV, Dh).transpose(1, 0, 2, 3, 4)

    def attend(carry, ci, k_i, v_i):
        m, l, acc = carry
        kv_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        if kv_stream:
            s = jnp.einsum(
                "bqkgd,bckd->bqkgc", qg, k_i, preferred_element_type=jnp.float32
            )
        else:
            s = jnp.einsum("bqkgd,bckd->bqkgc", qg, k_i.astype(jnp.float32))
        valid = kv_pos[None, :] < Skv  # padding mask [1, chunk]
        delta = q_pos[..., :, None] - kv_pos[None, :]  # [(B,) Sq, chunk]
        mask = valid
        if causal:
            mask = mask & (delta >= 0)
        mask = mask & (delta < window)
        if mask.ndim == 2:
            mask_b = mask[None, :, None, None, :]
        else:  # per-batch q offsets
            mask_b = mask[:, :, None, None, :]
        s = jnp.where(mask_b, s, NEG_INF)
        m_i = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_i)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        if kv_stream:
            pv = jnp.einsum(
                "bqkgc,bckd->bqkgd",
                p.astype(v_i.dtype),
                v_i,
                preferred_element_type=jnp.float32,
            )
        else:
            pv = jnp.einsum("bqkgc,bckd->bqkgd", p, v_i.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new)

    m0 = jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KV, G, Dh), jnp.float32)

    if kv_stream:
        def body(carry, ci):
            k_i = jax.lax.dynamic_slice_in_dim(k, ci * chunk, chunk, axis=1)
            v_i = jax.lax.dynamic_slice_in_dim(v, ci * chunk, chunk, axis=1)
            return attend(carry, ci, k_i, v_i), None

        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, acc0), jnp.arange(n_chunks, dtype=jnp.int32)
        )
    else:
        def body(carry, inp):
            ci, k_i, v_i = inp
            return attend(carry, ci, k_i, v_i), None

        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, acc0), (jnp.arange(n_chunks, dtype=jnp.int32), kc, vc)
        )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    window: jax.Array | int | None = None,
    mulsum: bool = False,
    kv_stream: bool = False,
) -> jax.Array:
    """One-token attention against a KV cache.

    q: [B,1,H,Dh]; caches: [B,Smax,KV,Dh]; cache_len: scalar int32 —
    number of valid cache entries *including* the token being decoded.

    ``mulsum=True``: compute scores/output with broadcast multiply +
    reduce rather than dot_general — GQA decode has arithmetic intensity
    ~G, far below the MXU roofline, and the dot's batch-dim layout forces
    XLA to materialize a transposed copy of the whole cache; the VPU
    mul-reduce streams the cache once in its stored layout.
    """
    B, _, H, Dh = q.shape
    _, Smax, KV, _ = k_cache.shape
    G = H // KV
    scale = Dh**-0.5
    if window is None:
        window = jnp.int32(2**30)
    window = jnp.asarray(window, jnp.int32)

    qg = q.reshape(B, KV, G, Dh).astype(jnp.float32) * scale
    pos = jnp.arange(Smax, dtype=jnp.int32)
    mask = (pos[None, :] < cache_len) & (pos[None, :] >= cache_len - window)
    if mulsum:
        # [B,S,KV,G] = sum_d k[B,S,KV,1,D] * q[B,1,KV,G,D]
        s = jnp.sum(
            k_cache.astype(jnp.float32)[:, :, :, None, :]
            * qg[:, None, :, :, :],
            axis=-1,
        )
        # Anchor the score layout to the cache layout (batch over data,
        # seq over model) — without this the partitioner replicates the
        # broadcasted product (iteration 1 regression, EXPERIMENTS.md).
        s = logical(s, ("cache_batch", "cache_seq", None, None))
        s = jnp.where(mask[:, :, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=1)
        out = jnp.sum(
            p[..., None] * v_cache.astype(jnp.float32)[:, :, :, None, :], axis=1
        )  # [B,KV,G,D]
        return out.reshape(B, 1, H, Dh).astype(q.dtype)
    if kv_stream:
        # bf16 operands, fp32 accumulation: any layout copies the dot
        # needs happen at bf16 width (2x less traffic than upcasting the
        # cache first); MXU accumulates fp32 natively.
        s = jnp.einsum(
            "bkgd,bskd->bkgs", qg.astype(k_cache.dtype), k_cache,
            preferred_element_type=jnp.float32,
        )
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum(
            "bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(B, 1, H, Dh).astype(q.dtype)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32))
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


def cross_attention_block(
    x: jax.Array,
    kv_cache: tuple[jax.Array, jax.Array],
    p: dict,
    cfg: ModelConfig,
):
    """Cross-attention against precomputed encoder K/V (no RoPE, no mask).

    x: [B,Sq,D]; kv_cache: (k, v) each [B,Skv,KV,Dh] from the encoder.
    """
    dtype = cfg.compute_dtype
    k, v = kv_cache
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
    out = chunked_attention(
        q, k, v, causal=False, window=None, chunk=cfg.attn_chunk
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))


def project_kv(x: jax.Array, p: dict, cfg: ModelConfig):
    """K/V projections only (encoder output -> cross-attention cache)."""
    dtype = cfg.compute_dtype
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return k, v


def _use_interpret() -> bool:
    """Pallas kernels execute for real on TPU, in interpret mode elsewhere."""
    return jax.default_backend() != "tpu"


def _per_head_shard(kernel, q, k, v, *rest, rows: bool = False):
    """Call a Pallas attention kernel under the ambient serving mesh.

    XLA cannot partition a Mosaic kernel. When a ``model`` mesh axis is
    in scope (the engine enters its replica's abstract mesh around each
    dispatch), the kernel runs under ``shard_map``: q [B, S, H, D] and
    k/v split over their heads, every other operand replicated. k/v are
    [..., KV, D] (caches, or pools whose rows keep the head axis) or,
    with ``rows``, page-row pools [..., KV * D], whose last axis holds
    the heads side by side and splits into whole heads. Heads that do
    not divide the axis run whole on every device. Without a mesh this
    is a plain call.
    """
    mesh = jax.sharding.get_abstract_mesh()
    width = 1 if mesh.empty else dict(mesh.shape).get("model", 1)
    if width == 1:
        return kernel(q, k, v, *rest)
    kv = k.shape[-1] // q.shape[-1] if rows else k.shape[-2]
    axis = "model" if q.shape[2] % width == 0 and kv % width == 0 else None
    heads = P(None, None, axis, None)
    kv_heads = P(*(None,) * (k.ndim - 1), axis) if rows else (
        P(*(None,) * (k.ndim - 2), axis, None)
    )
    return jax.shard_map(
        kernel,
        in_specs=(heads, kv_heads, kv_heads) + (P(),) * len(rest),
        out_specs=heads,
        check_vma=False,
    )(q, k, v, *rest)


def attention_block(
    x: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    window: jax.Array | int | None,
    cache: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    causal: bool = True,
    window_static: int | None = None,
):
    """Full attention sub-block: qkv -> attn -> o_proj.

    Without ``cache``: self-attention over x (train/prefill); returns
    (out, (k, v)) so prefill can populate the cache.
    With ``cache=(k_cache, v_cache, cache_len)``: single-token decode —
    computes k/v for the current token, writes them into the cache at
    ``cache_len - 1``, attends; returns (out, (k_cache, v_cache)).
    """
    dtype = cfg.compute_dtype
    q, k, v = _project_qkv(x, p, cfg, positions)
    q = logical(q, ("batch", "seq", "heads", "head_dim"))
    if cache is None:
        if cfg.attn_impl == "pallas":
            from ..kernels.flash_attention import flash_attention

            out = _per_head_shard(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, window=window_static,
                    interpret=_use_interpret(),
                ),
                q, k, v,
            )
        else:
            out = chunked_attention(
                q, k, v, causal=causal, window=window, chunk=cfg.attn_chunk,
                kv_stream=cfg.attn_kv_stream,
            )
        out = logical(out, ("batch", "seq", "heads", "head_dim"))
        o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))
        return o, (k, v)
    if len(cache) == 4:
        k_cache, v_cache, cache_len, write_idx = cache
    else:
        k_cache, v_cache, cache_len = cache
        write_idx = cache_len - 1  # plain cache: append position
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, write_idx, 0, 0)
    )
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, write_idx, 0, 0)
    )
    if cfg.attn_impl == "pallas":
        from ..kernels.decode_attention import decode_attention as decode_kernel

        out = _per_head_shard(
            lambda q, k, v, n: decode_kernel(
                q, k, v, n, window=window_static, interpret=_use_interpret()
            ),
            q, k_cache, v_cache, jnp.asarray(cache_len, jnp.int32),
        )
    else:
        out = decode_attention(
            q, k_cache, v_cache, cache_len, window=window,
            mulsum=cfg.decode_mulsum, kv_stream=cfg.attn_kv_stream,
        )
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))
    return o, (k_cache, v_cache)


def _pool_index(layer, write_pages, write_offs) -> tuple:
    """Where a layer's K/V rows land: ``[layer, page, offset]`` of a
    layer stack, or ``[page, offset]`` of one layer's pool (``layer``
    None)."""
    at = (write_pages, write_offs)
    return at if layer is None else (layer,) + at


def _scatter_kv_pages(
    pages: dict, at: tuple, k: jax.Array, v: jax.Array
) -> dict:
    """Write K/V rows into the pool at ``at`` (:func:`_pool_index`).

    ``pages``: {"k", "v"} (+ {"k_scale", "v_scale"} for int8 pools —
    the presence of scales *is* the quantization switch). A layer
    stack is the layer loop's carry, so its rows land in place. k/v
    rows are [..., KV, Dh], written in the pool's row shape: [KV, Dh],
    or [KV * Dh] for a pool kept as page rows (the serving engine's).
    int8 pools quantize each row at scatter time (per-row amax,
    :func:`repro.kernels.decode_attention.quantize_kv`) and store its
    fp32 scale alongside, so a row is quantized exactly once and never
    requantized.
    """

    def put(pool, rows):
        rows = rows.reshape(rows.shape[: rows.ndim - 2] + pool.shape[len(at):])
        return pool.at[at].set(rows.astype(pool.dtype))

    out = dict(pages)
    if "k_scale" in pages:
        from ..kernels.decode_attention import quantize_kv

        qk, ks = quantize_kv(k)
        qv, vs = quantize_kv(v)
        out["k"] = put(pages["k"], qk)
        out["v"] = put(pages["v"], qv)
        out["k_scale"] = pages["k_scale"].at[at].set(ks)
        out["v_scale"] = pages["v_scale"].at[at].set(vs)
    else:
        out["k"] = put(pages["k"], k)
        out["v"] = put(pages["v"], v)
    return out


def paged_attention_block(
    x: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # [B, 1] per-request absolute position (>= 0)
    pages: dict,  # {"k","v"[,"k_scale","v_scale"]} pool stack (or one layer)
    block_tables: jax.Array,  # [B, NB] int32
    write_pages: jax.Array,  # [B] physical page for this token's K/V
    write_offs: jax.Array,  # [B] offset within that page
    layer: jax.Array | None = None,  # int32 scalar: this layer of the stack
):
    """Single-token attention sub-block against a paged KV pool.

    The batch dimension is the engine's slot width: every request has
    its own context length (``positions``) and block table. The new
    token's K/V land at (layer, write_pages, write_offs) of the pool
    stack, precomputed by :func:`repro.models.transformer.decode_step_paged`
    (layer-invariant; masked lanes point at the pool's scratch page so a
    batched scatter never corrupts a live page), and attention reads
    layer ``layer`` of the stack in place; with ``layer`` None, ``pages``
    is one layer's pool. int8 pools (``k_scale`` present) quantize at
    scatter and dequantize inside the page gather — kernel and fallback
    alike. Returns (out [B,1,D], updated pages).
    """
    dtype = cfg.compute_dtype
    q, k, v = _project_qkv(x, p, cfg, positions)
    at = _pool_index(layer, write_pages, write_offs)
    pages = _scatter_kv_pages(pages, at, k[:, 0], v[:, 0])
    kp, vp = pages["k"], pages["v"]
    ks, vs = pages.get("k_scale"), pages.get("v_scale")
    attn_len = positions[:, 0] + 1  # valid entries incl. the new token
    if cfg.attn_impl == "pallas":
        from ..kernels.decode_attention import paged_decode_attention

        out = _per_head_shard(
            lambda q, k, v, bt, n, li, ks, vs: paged_decode_attention(
                q, k, v, bt, n, layer=li, k_scales=ks, v_scales=vs,
                interpret=_use_interpret(),
            ),
            q, kp, vp, block_tables, attn_len, layer, ks, vs,
            rows=kp.ndim == len(at) + 1,
        )
    elif cfg.decode_mulsum or cfg.attn_kv_stream:
        # The dense-decode perf variants over the gathered pages
        # (dequantized for int8 pools; [B,1] lengths broadcast against
        # the position row).
        from ..kernels.decode_attention import gather_pages

        Dh = cfg.head_dim
        out = decode_attention(
            q, gather_pages(kp, block_tables, ks, layer, Dh),
            gather_pages(vp, block_tables, vs, layer, Dh), attn_len[:, None],
            mulsum=cfg.decode_mulsum, kv_stream=cfg.attn_kv_stream,
        )
    else:
        # The chunk fallback with one query per lane: decode and a
        # speculative verify chunk share one reduction, so verify is
        # exact against sequential decode.
        from ..kernels.decode_attention import paged_prefill_attention

        out = paged_prefill_attention(
            q, kp, vp, block_tables, positions[:, 0],
            layer=layer, k_scales=ks, v_scales=vs,
        )
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))
    return o, pages


def chunk_attention_block(
    x: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    offset: jax.Array,  # scalar (vmapped lane) or [B] absolute chunk start
    k_cache: jax.Array,  # [B, L, KV, Dh] dense per-request cache
    v_cache: jax.Array,
):
    """Multi-token prefill-continuation sub-block against a dense cache.

    The chunked-prefill middle ground between :func:`attention_block`'s
    two modes: like prefill it processes ``C = x.shape[1]`` new tokens,
    like decode it extends an existing cache. K/V for the chunk are
    scattered at absolute positions ``offset .. offset + C - 1``
    (out-of-bounds padding writes are dropped), then the chunk attends
    causally over the whole cache with ``q_offset = offset`` — every
    earlier entry is real by construction, and queries past the caller's
    valid count produce garbage the engine discards. Returns
    (out [B, C, D], (k_cache, v_cache)).
    """
    dtype = cfg.compute_dtype
    B, C = x.shape[:2]
    offset = jnp.asarray(offset, jnp.int32)
    positions = offset[..., None] + jnp.arange(C, dtype=jnp.int32)  # [(B,) C]
    q, k, v = _project_qkv(x, p, cfg, positions)
    idx = jnp.broadcast_to(positions, (B, C))
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    k_cache = k_cache.at[rows, idx].set(k.astype(k_cache.dtype), mode="drop")
    v_cache = v_cache.at[rows, idx].set(v.astype(v_cache.dtype), mode="drop")
    out = chunked_attention(
        q, k_cache, v_cache, causal=True, q_offset=offset,
        chunk=cfg.attn_chunk, kv_stream=cfg.attn_kv_stream,
    )
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))
    return o, (k_cache, v_cache)


def paged_chunk_attention_block(
    x: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # [B, C] absolute position per chunk token
    pages: dict,  # {"k","v"[,"k_scale","v_scale"]} pool stack (or one layer)
    block_tables: jax.Array,  # [B, NB] int32
    write_pages: jax.Array,  # [B, C] physical page per chunk token
    write_offs: jax.Array,  # [B, C] offset within that page
    layer: jax.Array | None = None,  # int32 scalar: this layer of the stack
):
    """Chunked-prefill sub-block against a paged KV pool.

    The paged sibling of :func:`chunk_attention_block`: the chunk's K/V
    are scattered into each request's reserved pages of layer ``layer``
    of the pool stack, or of one layer's pool with ``layer`` None
    (masked lanes and padding positions land on the
    scratch page, precomputed by
    :func:`repro.models.transformer.prefill_chunk_paged`; int8 pools
    quantize per row at scatter), then the chunk attends over the paged
    prefix. On the Pallas path that is
    :func:`repro.kernels.decode_attention.paged_prefill_attention_pallas`
    — the block-table walk happens in the kernel's DMA index map, so no
    contiguous copy of the prefix, nor a slice of the layer, is ever
    materialized; off TPU the gather fallback computes the identical
    masked softmax. Returns (out [B, C, D], updated pages).
    """
    dtype = cfg.compute_dtype
    q, k, v = _project_qkv(x, p, cfg, positions)
    at = _pool_index(layer, write_pages, write_offs)
    pages = _scatter_kv_pages(pages, at, k, v)
    kp, vp = pages["k"], pages["v"]
    ks, vs = pages.get("k_scale"), pages.get("v_scale")
    if cfg.attn_impl == "pallas":
        from ..kernels.decode_attention import paged_prefill_attention_pallas

        out = _per_head_shard(
            lambda q, k, v, bt, off, li, ks, vs: paged_prefill_attention_pallas(
                q, k, v, bt, off, layer=li, k_scales=ks, v_scales=vs,
                interpret=_use_interpret(),
            ),
            q, kp, vp, block_tables, positions[:, 0], layer, ks, vs,
            rows=kp.ndim == len(at) + 1,
        )
    else:
        from ..kernels.decode_attention import paged_prefill_attention

        out = paged_prefill_attention(
            q, kp, vp, block_tables, positions[:, 0],
            layer=layer, k_scales=ks, v_scales=vs,
        )
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dtype))
    return o, pages
