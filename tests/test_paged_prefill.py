"""Pallas paged prefill kernel: oracle/dense parity, int8 dequant, the
no-materialized-gather acceptance (jaxpr inspection — the Pallas path's
block-table walk happens in the kernel's DMA index map, so the traced
computation contains no XLA gather over the pool), and model-level
chunk-vs-prefill equality on the Pallas path."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import tiny_model

from repro.analysis import count_primitive
from repro.kernels.decode_attention import (
    paged_prefill_attention,
    paged_prefill_attention_pallas,
    quantize_kv,
)
from repro.models.attention import chunked_attention, paged_chunk_attention_block


def _scattered_cache(rng, B, NB, page, KV, D, spare=2):
    """A contiguous per-request cache scattered over a shuffled pool."""
    S = NB * page
    P = B * NB + spare
    k_dense = rng.normal(size=(B, S, KV, D)).astype(np.float32)
    v_dense = rng.normal(size=(B, S, KV, D)).astype(np.float32)
    bt = rng.permutation(P)[: B * NB].reshape(B, NB).astype(np.int32)
    k_pages = rng.normal(size=(P, page, KV, D)).astype(np.float32)  # garbage
    v_pages = rng.normal(size=(P, page, KV, D)).astype(np.float32)
    for b in range(B):
        for j in range(NB):
            k_pages[bt[b, j]] = k_dense[b, j * page : (j + 1) * page]
            v_pages[bt[b, j]] = v_dense[b, j * page : (j + 1) * page]
    return k_dense, v_dense, k_pages, v_pages, bt


class TestPagedPrefillKernel:
    def test_matches_oracle_and_dense(self):
        """Kernel == gather oracle == dense chunked_attention, under
        arbitrary page scatter and ragged per-lane offsets."""
        rng = np.random.default_rng(0)
        B, C, KV, G, D, page, NB = 3, 5, 2, 3, 8, 4, 6
        H = KV * G
        S = NB * page
        k_dense, v_dense, k_pages, v_pages, bt = _scattered_cache(
            rng, B, NB, page, KV, D
        )
        q = rng.normal(size=(B, C, H, D)).astype(np.float32)
        offs = np.array([0, 7, S - C], np.int32)  # ragged lane offsets

        out = paged_prefill_attention_pallas(
            jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(bt), jnp.asarray(offs), interpret=True,
        )
        ref = paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(bt), jnp.asarray(offs),
        )
        dense = chunked_attention(
            jnp.asarray(q), jnp.asarray(k_dense), jnp.asarray(v_dense),
            causal=True, q_offset=jnp.asarray(offs), chunk=8,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense), rtol=3e-5, atol=3e-5
        )
        assert np.all(np.isfinite(np.asarray(out)))

    def test_int8_pages_match_oracle(self):
        """Kernel and fallback dequantize identically (both read the
        same int8 rows + per-row scales), and int8 error vs fp32 stays
        at quantization scale."""
        rng = np.random.default_rng(1)
        B, C, KV, G, D, page, NB = 2, 4, 1, 4, 8, 8, 3
        H = KV * G
        _, _, k_pages, v_pages, bt = _scattered_cache(rng, B, NB, page, KV, D)
        q = rng.normal(size=(B, C, H, D)).astype(np.float32)
        offs = np.array([0, 5], np.int32)
        qk, ks = quantize_kv(jnp.asarray(k_pages))
        qv, vs = quantize_kv(jnp.asarray(v_pages))

        out = paged_prefill_attention_pallas(
            jnp.asarray(q), qk, qv, jnp.asarray(bt), jnp.asarray(offs),
            k_scales=ks, v_scales=vs, interpret=True,
        )
        ref = paged_prefill_attention(
            jnp.asarray(q), qk, qv, jnp.asarray(bt), jnp.asarray(offs),
            k_scales=ks, v_scales=vs,
        )
        fp = paged_prefill_attention_pallas(
            jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(bt), jnp.asarray(offs), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5
        )
        assert float(np.max(np.abs(np.asarray(out) - np.asarray(fp)))) < 0.05


class TestNoMaterializedGather:
    """Acceptance: chunked paged prefill no longer materializes a
    ``gather_pages`` copy when the Pallas path is active."""

    def _trace(self, impl):
        cfg, model, params = tiny_model()
        cfg = dataclasses.replace(cfg, attn_impl=impl)
        p_layer = jax.tree_util.tree_map(
            lambda a: a[0], params["classes"]["c0"]["attn"]
        )
        W, C, page, P = 2, 4, 8, 6
        KV, Dh = cfg.n_kv_heads, cfg.head_dim
        pages = {
            "k": jnp.zeros((P + 1, page, KV, Dh), jnp.float32),
            "v": jnp.zeros((P + 1, page, KV, Dh), jnp.float32),
        }
        bt = jnp.asarray(np.arange(W * 3).reshape(W, 3).astype(np.int32))
        positions = jnp.asarray(np.tile(np.arange(C), (W, 1)).astype(np.int32))
        x = jnp.zeros((W, C, cfg.d_model), jnp.float32)
        wp = jnp.zeros((W, C), jnp.int32)
        wo = positions % page

        fn = functools.partial(
            paged_chunk_attention_block, p=p_layer, cfg=cfg,
            positions=positions, pages=pages, block_tables=bt,
            write_pages=wp, write_offs=wo,
        )
        return jax.make_jaxpr(lambda x: fn(x))(x)

    def test_pallas_path_has_no_gather(self):
        fallback = self._trace("xla")
        pallas = self._trace("pallas")
        # The fallback's gather_pages materializes the prefix: >= 2 XLA
        # gathers (K and V pools). The Pallas path's page walk lives in
        # the kernel's BlockSpec index map — zero gathers in the trace.
        assert count_primitive(fallback.jaxpr, "gather") >= 2
        assert count_primitive(pallas.jaxpr, "gather") == 0
        # Both still scatter the chunk's K/V into the pool.
        assert count_primitive(pallas.jaxpr, "scatter") >= 2


class TestPallasChunkModelParity:
    def test_chunk_steps_match_whole_prefill_pallas(self):
        """Model-level: driving prefill_chunk_paged chunk-by-chunk on
        the Pallas path (interpret) matches whole-prompt dense prefill
        logits at the final position."""
        cfg, model, params = tiny_model()
        cfg_p = dataclasses.replace(cfg, attn_impl="pallas")
        from repro.models import build_model

        model_p = build_model(cfg_p)
        S, C, page, W = 11, 4, 8, 2
        NB = 3
        prompt = (np.arange(S) * 5 + 2) % cfg.vocab_size
        ref_logits, _ = model.prefill(
            params, {"tokens": jnp.asarray(prompt)[None]}, 32
        )

        shape = (cfg.n_layers, W * NB + 1, page, cfg.n_kv_heads, cfg.head_dim)
        pools = {
            "k": jnp.zeros(shape, jnp.float32),
            "v": jnp.zeros(shape, jnp.float32),
        }
        bt = jnp.asarray(np.arange(W * NB).reshape(W, NB).astype(np.int32))
        pos = 0
        while pos < S:
            valid = min(C, S - pos)
            buf = np.zeros((W, C), np.int32)
            buf[0, :valid] = prompt[pos : pos + valid]
            offs = jnp.asarray(np.array([pos, -1], np.int32))
            valids = jnp.asarray(np.array([valid, 0], np.int32))
            out, pools = model_p.prefill_chunk_paged(
                params, jnp.asarray(buf), pools, offs, valids, bt
            )
            pos += valid
        np.testing.assert_allclose(
            np.asarray(out[0, valid - 1]),
            np.asarray(ref_logits[0, -1]),
            rtol=2e-4, atol=2e-4,
        )


def _scan_formulation(kind, params, inp, pools, lens_or_offs, valids, bt, cfg):
    """The paged layer loop as a scan over the pool stack: each layer's
    pool sliced out as ``xs`` and the updated layers stacked as ``ys``.
    The same write coordinates as the model; the reference for the loop
    that carries the stack and updates it in place."""
    from repro.models import transformer as T
    from repro.models.attention import paged_attention_block

    x = T._embed(params, inp, cfg)
    W = x.shape[0]
    page, scratch = pools["k"].shape[2], pools["k"].shape[1] - 1
    pos0 = jnp.maximum(lens_or_offs, 0)
    if kind == "decode":
        positions = pos0[:, None]
        write_pages = jnp.where(
            lens_or_offs >= 0, bt[jnp.arange(W), pos0 // page], scratch
        )
        block = paged_attention_block
    else:
        C = x.shape[1]
        positions = pos0[:, None] + jnp.arange(C, dtype=jnp.int32)
        writable = (lens_or_offs >= 0)[:, None] & (
            jnp.arange(C)[None, :] < valids[:, None]
        )
        rows = jnp.arange(W, dtype=jnp.int32)[:, None]
        table = bt[rows, jnp.minimum(positions // page, bt.shape[1] - 1)]
        write_pages = jnp.where(writable, table, scratch)
        block = paged_chunk_attention_block

    def body(x, scanned):
        p_layer, pages = scanned
        h = T.rmsnorm(x, p_layer["ln1"], cfg.rms_eps)
        a, pages = block(
            h, p_layer["attn"], cfg, positions=positions, pages=pages,
            block_tables=bt, write_pages=write_pages,
            write_offs=positions[:, 0] % page if kind == "decode" else positions % page,
        )
        x = x + a
        ff, _ = T._ffn(T.rmsnorm(x, p_layer["ln2"], cfg.rms_eps), p_layer, cfg)
        return x + ff, pages

    x, pools = jax.lax.scan(body, x, (params["classes"]["c0"], pools))
    return T._unembed(params, x, cfg), pools


class TestStackedPoolLoop:
    """The layer loop carries the pool stack and each layer scatters
    into, and attends over, its own layer in place: outputs and pools
    equal the scan-over-layers formulation bit for bit, for a pool kept
    as page rows ``[L, P+1, page, KV * Dh]`` (the engine's)."""

    @pytest.mark.parametrize(
        "impl,kv", [("xla", "float32"), ("pallas", "float32"), ("pallas", "int8")],
        ids=["xla", "pallas", "pallas-int8"],
    )
    @pytest.mark.parametrize("kind", ["decode", "chunk"])
    def test_matches_scan_formulation(self, kind, impl, kv):
        from repro.models import build_model

        cfg, _, params = tiny_model()
        cfg = dataclasses.replace(cfg, attn_impl=impl)
        model = build_model(cfg)
        rng = np.random.default_rng(7)
        W, NB, page, C = 3, 3, 8, 4
        P = W * NB + 2
        L, KV, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        heads = {}
        for name in ("k", "v"):
            rows = rng.normal(size=(L, P + 1, page, KV, Dh)).astype(np.float32)
            heads[name] = jnp.asarray(rows)
        if kv == "int8":
            for name in ("k", "v"):
                heads[name], heads[f"{name}_scale"] = quantize_kv(heads[name])
        bt = jnp.asarray(rng.permutation(P)[: W * NB].reshape(W, NB).astype(np.int32))
        if kind == "decode":
            inp = jnp.asarray(rng.integers(0, cfg.vocab_size, (W, 1)), jnp.int32)
            lens = jnp.asarray([5, -1, NB * page - 1], jnp.int32)
            valids = None
            step = lambda pools: model.decode_paged(params, inp, pools, lens, bt)
        else:
            inp = jnp.asarray(rng.integers(0, cfg.vocab_size, (W, C)), jnp.int32)
            lens = jnp.asarray([3, -1, NB * page - C], jnp.int32)
            valids = jnp.asarray([C, 0, C - 1], jnp.int32)
            step = lambda pools: model.prefill_chunk_paged(
                params, inp, pools, lens, valids, bt
            )
        as_rows = {
            name: a.reshape(a.shape[:3] + (-1,)) if name in ("k", "v") else a
            for name, a in heads.items()
        }
        out, pools = jax.jit(step)(as_rows)
        ref_out, ref_pools = jax.jit(
            lambda pools: _scan_formulation(kind, params, inp, pools, lens, valids, bt, cfg)
        )(heads)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
        assert set(pools) == set(ref_pools)
        for name, a in pools.items():
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(ref_pools[name]).reshape(a.shape)
            )
