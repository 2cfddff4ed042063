"""Pallas TPU attention over a paged KV cache (block-table walk).

Serving keeps each replica's KV cache as a shared pool of fixed-size
pages (``serving/cache.py``); a request's context is scattered over
non-contiguous pages named by its block table. One kernel,
:func:`paged_attention`, lets C query tokens per sequence attend to that
scattered cache without ever materializing a contiguous copy:

* the grid is (batch, kv-head block, logical block) and the block table
  is a *scalar-prefetch* operand, so each cell's BlockSpec ``index_map``
  resolves the logical block to its physical page and the DMA fetches
  exactly that page — the gather happens in the memory system;
* the pool is the stage's whole layer stack, as the model's layer loop
  carries it, and the layer index is one more scalar-prefetch operand:
  the index map reads page ``bt[b, c]`` of layer ``layer`` straight from
  the stack, so no per-layer slice of the pool is ever made (a one-layer
  pool ``[P, page, KV, D]`` is read as a stack of one);
* the per-lane query offsets are scalar-prefetch too: query ``i`` of
  lane ``b`` sits at ``offsets[b] + i`` and attends the positions
  ``<= offsets[b] + i`` (and, with ``window``, ``> offsets[b] + i -
  window``), masked in-kernel;
* the softmax runs online across the logical blocks (the last grid
  axis) in VMEM scratch, so no per-block partials go to HBM.

The kernel reads the pool as page rows, ``[L, P, page, KV * D]``: a
position's heads side by side, which is how the serving engine keeps
it. The TPU tiles an array's last two axes by (8, 128); with ``(KV, D)``
last, a D of 64 would pad every row to twice its bytes, so the TPU's own
layout for such a pool puts the page axis last, and every kernel call
would copy the pool into the layout the kernel reads. A page block
holds every row of the page for ``Hb`` KV heads, ``Hb`` a multiple of 8
or all the heads, so its lanes are whole tiles. The block is converted
to fp32 once and each head's ``D`` lanes sliced out of it.

Decode is the C = 1 case (:func:`paged_decode_attention`); chunked
prefill over a paged prefix is the general case
(:func:`paged_prefill_attention_pallas`); and dense decode over a
contiguous per-request cache runs the same kernel with an identity
block table (:mod:`.ops`).

Logical blocks past a lane's last query (or before its window) skip
their compute; out-of-range logical blocks point at the pool's reserved
scratch page. int8 pools (``kv_dtype="int8"`` serving) carry one fp32
scale per page row, ``[L, P, page]``; passing ``k_scales``/``v_scales``
makes the kernel apply them to the scores and the probabilities in VMEM,
so quantized attention reads a quarter of the fp32 bytes and never
materializes an fp copy of the cache. A scale block is the 8 pages
around ``bt[b, c]`` (the TPU tiles a block's second-to-last axis by 8),
and the kernel picks the page's row out of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import dot_rows, page_rows

NEG_INF = -1e30


def _heads_per_block(kv_heads: int) -> int:
    """KV heads per page block: a multiple of the 8-row tile, or all."""
    return 8 if kv_heads % 8 == 0 else kv_heads


def _paged_kernel(
    layer_ref,  # [1] int32 scalar-prefetch: the pool stack's layer
    bt_ref,  # [B, NB] int32 scalar-prefetch: logical block -> physical page
    off_ref,  # [B] int32 scalar-prefetch: absolute position of q[:, 0]
    q_ref,  # [1, Hb, G, C, D] fp32, pre-scaled by 1/sqrt(D)
    k_ref,  # [1, page, Hb * D] — the physical page named by bt[b, c]
    v_ref,
    *refs,  # ([ks_ref, vs_ref] [SB, page] when quantized), o_ref, scratch
    page_size: int,
    window: int | None,
    quantized: bool,
):
    del layer_ref  # read by the index maps only
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    ci = pl.program_id(2)
    _, Hb, G, C, D = q_ref.shape
    off = off_ref[b]
    first = ci * page_size

    @pl.when(ci == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    live = first <= off + C - 1
    if window is not None:
        live = live & (first + page_size > off - window + 1)

    @pl.when(live)
    def _block():
        kv_pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        q_pos = off + jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        mask = kv_pos <= q_pos  # [C, page]
        if window is not None:
            mask = mask & (kv_pos > q_pos - window)
        neg = jnp.full(mask.shape, NEG_INF, jnp.float32)
        zero = jnp.zeros(mask.shape, jnp.float32)
        k_all = k_ref[0].astype(jnp.float32)  # [page, Hb * D]
        v_all = v_ref[0].astype(jnp.float32)
        if quantized:
            # The page's scale row within its block of SB pages.
            sb = ks_ref.shape[0]
            row = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape, 0)
            mine = row == jax.lax.rem(bt_ref[b, ci], sb)
            none = jnp.zeros(ks_ref.shape, jnp.float32)
            k_s = jnp.sum(jax.lax.select(mine, ks_ref[...], none), 0, keepdims=True)
            v_s = jnp.sum(jax.lax.select(mine, vs_ref[...], none), 0, keepdims=True)
        for h in range(Hb):
            k = k_all[:, h * D : (h + 1) * D]  # [page, D]
            v = v_all[:, h * D : (h + 1) * D]
            for g in range(G):
                i = h * G + g
                s = jax.lax.dot_general(
                    q_ref[0, h, g], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [C, page]
                if quantized:
                    s = s * k_s
                s = jnp.where(mask, s, neg)
                m_prev = m_scr[i]  # [C, 1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.where(mask, jnp.exp(s - m_new), zero)
                l_scr[i] = alpha * l_scr[i] + jnp.sum(p, axis=1, keepdims=True)
                if quantized:
                    p = p * v_s
                acc_scr[i] = alpha * acc_scr[i] + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [C, D]
                m_scr[i] = m_new

    @pl.when(ci == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], jnp.full(l_scr.shape, 1e-30, jnp.float32))
        o_ref[0] = (acc_scr[...] / l).reshape(o_ref.shape[1:])


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(
    q: jax.Array,  # [B, C, H, D] (model layout) — C query tokens per lane
    k_pages: jax.Array,  # [P, page, KV, D] one layer's pool; with layer, a stack
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, NB] int32 physical page per logical block
    offsets: jax.Array,  # [B] int32 absolute position of q[:, 0]
    *,
    layer: jax.Array | None = None,  # int32 scalar: the stack's layer to read
    window: int | None = None,
    k_scales: jax.Array | None = None,  # [(L,) P, page] fp32 per-row scales (int8)
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of C queries per lane over a paged cache.

    With ``layer``, ``k_pages``/``v_pages`` are the stage's layer stack,
    ``[L, P, page, KV * D]`` page rows (or ``[L, P, page, KV, D]``), and
    the kernel reads layer ``layer`` of it in place; without, one layer's
    pool ``[P, page, KV, D]``, read as a stack of one.

    Returns [B, C, H, D]. Rows whose position has no valid entry (a
    negative offset) come out as zeros; rows past the caller's valid
    count produce values the caller discards.
    """
    k_rows, k_scales, li = page_rows(k_pages, k_scales, layer)
    v_rows, v_scales, _ = page_rows(v_pages, v_scales, layer)
    B, C_out, H, D = q.shape
    _, P, page, R = k_rows.shape
    KV = R // D
    NB = block_tables.shape[1]
    G = H // KV
    Hb = _heads_per_block(KV)
    quantized = k_scales is not None

    # Interpret mode runs the kernel's dots on XLA:CPU (see dot_rows).
    C = dot_rows(C_out, interpret)
    q = jnp.pad(q, ((0, 0), (0, C - C_out), (0, 0), (0, 0)))
    qg = (q.astype(jnp.float32) * D**-0.5).reshape(B, C, KV, G, D)
    qg = qg.transpose(0, 2, 3, 1, 4)  # [B, KV, G, C, D]
    kernel = functools.partial(
        _paged_kernel, page_size=page, window=window, quantized=quantized
    )
    page_spec = pl.BlockSpec(
        (None, 1, page, Hb * D),
        lambda b, h, c, li, bt, off: (li[0], bt[b, c], 0, h),
    )
    head_spec = pl.BlockSpec(
        (1, Hb, G, C, D), lambda b, h, c, li, bt, off: (b, h, 0, 0, 0)
    )
    in_specs = [head_spec, page_spec, page_spec]
    operands = [qg, k_rows, v_rows]
    if quantized:
        sb = min(8, P)  # pages per scale block: the sublane tile, or all
        scale_spec = pl.BlockSpec(
            (None, sb, page),
            lambda b, h, c, li, bt, off: (li[0], bt[b, c] // sb, 0),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV // Hb, NB),
        in_specs=in_specs,
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((Hb * G, C, 1), jnp.float32),
            pltpu.VMEM((Hb * G, C, 1), jnp.float32),
            pltpu.VMEM((Hb * G, C, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, C, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_attention",  # the kernel's op name in a profiler trace
    )(
        jnp.asarray(li, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32),
        offsets.astype(jnp.int32),
        *operands,
    )
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, D)[:, :C_out]
    return out.astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,  # [B, 1, H, D]
    k_pages: jax.Array,  # [P, page, KV, D]; with layer, a stack (paged_attention)
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, NB] int32 physical page per logical block
    lengths: jax.Array,  # [B] int32 valid entries incl. current token
    *,
    layer: jax.Array | None = None,
    window: int | None = None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token attention against a paged KV cache. Returns [B,1,H,D]."""
    return paged_attention(
        q, k_pages, v_pages, block_tables, jnp.asarray(lengths, jnp.int32) - 1,
        layer=layer, window=window, k_scales=k_scales, v_scales=v_scales,
        interpret=interpret,
    )


def paged_prefill_attention_pallas(
    q: jax.Array,  # [B, C, H, D] (model layout) — C new tokens per lane
    k_pages: jax.Array,  # [P, page, KV, D]; with layer, a stack (paged_attention)
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, NB] int32 physical page per logical block
    offsets: jax.Array,  # [B] int32 absolute position of q[:, 0] (>= 0)
    *,
    layer: jax.Array | None = None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Chunk attention over a paged prefix, gather-free. Returns [B,C,H,D].

    Drop-in for :func:`.ref.paged_prefill_attention` (the XLA gather
    fallback, which stays as the off-TPU path and test oracle)."""
    return paged_attention(
        q, k_pages, v_pages, block_tables, offsets, layer=layer,
        k_scales=k_scales, v_scales=v_scales, interpret=interpret,
    )
