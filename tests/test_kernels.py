"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept across shapes and dtypes per the deliverable-(c) requirement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention, decode_attention_ref
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref
from repro.kernels.selective_scan import selective_scan, selective_scan_ref


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Sq,Skv,H,KV,D,causal,window",
        [
            (2, 64, 64, 4, 2, 32, True, None),  # GQA causal
            (1, 96, 96, 4, 4, 64, True, None),  # MHA
            (2, 64, 64, 8, 1, 32, True, None),  # MQA
            (1, 100, 100, 4, 4, 16, True, None),  # ragged tail (padding)
            (2, 64, 64, 4, 2, 32, False, None),  # bidirectional (encoder)
            (1, 128, 128, 2, 2, 32, True, 48),  # sliding window
            (1, 160, 160, 5, 1, 32, True, 64),  # window + MQA + ragged
        ],
    )
    def test_matches_oracle(self, B, Sq, Skv, H, KV, D, causal, window, dtype):
        ks = jax.random.split(jax.random.PRNGKey(42), 3)
        q = rand(ks[0], (B, Sq, H, D), dtype)
        k = rand(ks[1], (B, Skv, KV, D), dtype)
        v = rand(ks[2], (B, Skv, KV, D), dtype)
        out = flash_attention(
            q, k, v, causal=causal, window=window,
            block_q=32, block_kv=32, interpret=True,
        )
        ref = flash_attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
        )

    @pytest.mark.parametrize("block_q,block_kv", [(16, 16), (32, 64), (64, 32)])
    def test_block_shape_invariance(self, block_q, block_kv):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = rand(ks[0], (1, 128, 4, 32), jnp.float32)
        k = rand(ks[1], (1, 128, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 128, 2, 32), jnp.float32)
        out = flash_attention(
            q, k, v, block_q=block_q, block_kv=block_kv, interpret=True
        )
        ref = flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,H,KV,D,length,window,chunk",
        [
            (2, 256, 4, 2, 32, 256, None, 64),  # full cache
            (2, 256, 4, 2, 32, 100, None, 64),  # partial cache
            (1, 512, 8, 1, 64, 300, None, 128),  # MQA long
            (2, 256, 4, 4, 32, 200, 64, 64),  # sliding window
            (1, 130, 2, 2, 16, 77, None, 64),  # ragged chunks
        ],
    )
    def test_matches_oracle(self, B, S, H, KV, D, length, window, chunk, dtype):
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = rand(ks[0], (B, 1, H, D), dtype)
        kc = rand(ks[1], (B, S, KV, D), dtype)
        vc = rand(ks[2], (B, S, KV, D), dtype)
        lengths = jnp.array([length] * B, jnp.int32)
        out = decode_attention(
            q, kc, vc, lengths, window=window, chunk=chunk, interpret=True
        )
        ref = decode_attention_ref(q, kc, vc, lengths, window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
        )

    def test_per_sequence_lengths(self):
        """Continuous batching: each row has its own cache length."""
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        B, S, H, KV, D = 4, 128, 4, 2, 32
        q = rand(ks[0], (B, 1, H, D), jnp.float32)
        kc = rand(ks[1], (B, S, KV, D), jnp.float32)
        vc = rand(ks[2], (B, S, KV, D), jnp.float32)
        lengths = jnp.array([1, 37, 100, 128], jnp.int32)
        out = decode_attention(q, kc, vc, lengths, chunk=32, interpret=True)
        ref = decode_attention_ref(q, kc, vc, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


class TestPagedAttention:
    """The one paged kernel at the page sizes and KV dtypes served on the
    chip: a block of 8 KV heads (KV=16) and a block of all heads (KV=4),
    decode (C=1) and a prefill chunk, int8 pages with their scales."""

    @pytest.mark.parametrize("page", [16, 32])
    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    @pytest.mark.parametrize("KV,G,C", [(16, 1, 1), (4, 2, 5)])
    def test_matches_oracle(self, page, kv_dtype, KV, G, C):
        from repro.kernels.decode_attention import (
            paged_attention,
            paged_prefill_attention,
            quantize_kv,
        )

        B, NB, D = 2, 3, 64
        P = B * NB + 1
        ks = jax.random.split(jax.random.PRNGKey(page + C), 3)
        q = rand(ks[0], (B, C, KV * G, D), jnp.float32)
        kp = rand(ks[1], (P, page, KV, D), jnp.float32)
        vp = rand(ks[2], (P, page, KV, D), jnp.float32)
        scales = {}
        if kv_dtype == "int8":
            kp, k_s = quantize_kv(kp)
            vp, v_s = quantize_kv(vp)
            scales = dict(k_scales=k_s, v_scales=v_s)
        bt = jnp.asarray(np.random.default_rng(page).permutation(P - 1)[: B * NB])
        bt = bt.reshape(B, NB).astype(jnp.int32)
        offsets = jnp.asarray([page - C, NB * page - C], jnp.int32)
        out = paged_attention(q, kp, vp, bt, offsets, interpret=True, **scales)
        ref = paged_prefill_attention(q, kp, vp, bt, offsets, **scales)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)

    @pytest.mark.parametrize("rows", [True, False], ids=["rows", "heads"])
    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    @pytest.mark.parametrize("KV,G,C", [(32, 1, 1), (8, 4, 5)], ids=["mha", "gqa"])
    def test_stacked_pool_reads_its_layer(self, KV, G, C, kv_dtype, rows):
        """Over a layer stack, the kernel at layer ``l`` is the kernel
        over ``pool[l]`` bit for bit, and the fallback over the stack is
        the fallback over ``pool[l]``. The stack is kept as page rows
        ``[L, P, page, KV * D]`` (the engine's) or ``[L, P, page, KV, D]``;
        11 pages put the scratch page in a partial block of int8 scales."""
        from repro.kernels.decode_attention import (
            paged_attention,
            paged_prefill_attention,
            quantize_kv,
        )

        L, layer, B, NB, D, page = 3, 1, 2, 5, 64, 16
        P = B * NB + 1
        ks = jax.random.split(jax.random.PRNGKey(KV + C), 3)
        q = rand(ks[0], (B, C, KV * G, D), jnp.float32)
        kp = rand(ks[1], (L, P, page, KV, D), jnp.float32)
        vp = rand(ks[2], (L, P, page, KV, D), jnp.float32)
        stack, one = {}, {}
        if kv_dtype == "int8":
            kp, stack["k_scales"] = quantize_kv(kp)
            vp, stack["v_scales"] = quantize_kv(vp)
            one = {name: s[layer] for name, s in stack.items()}
        else:
            kp, vp = kp.astype(kv_dtype), vp.astype(kv_dtype)
        k_one, v_one = kp[layer], vp[layer]
        if rows:
            kp = kp.reshape(L, P, page, KV * D)
            vp = vp.reshape(L, P, page, KV * D)
        # The scratch page (P - 1) and the page before it in the table.
        bt = jnp.asarray(np.random.default_rng(KV).permutation(P)[: B * NB])
        bt = bt.at[0].set(P - 1).reshape(B, NB).astype(jnp.int32)
        offsets = jnp.asarray([page - C, NB * page - C], jnp.int32)
        li = jnp.int32(layer)

        got = paged_attention(q, kp, vp, bt, offsets, layer=li, interpret=True, **stack)
        want = paged_attention(q, k_one, v_one, bt, offsets, interpret=True, **one)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        ref = paged_prefill_attention(q, kp, vp, bt, offsets, layer=li, **stack)
        ref_one = paged_prefill_attention(q, k_one, v_one, bt, offsets, **one)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ref_one))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5, atol=3e-5)


class TestRMSNorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("R,D", [(8, 128), (100, 256), (1, 512), (300, 64)])
    def test_matches_oracle(self, R, D, dtype):
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        x = rand(ks[0], (R, D), dtype)
        w = rand(ks[1], (D,), jnp.float32) * 0.1 + 1.0
        out = rmsnorm(x, w, block_rows=32, interpret=True)
        ref = rmsnorm_ref(x, w)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
        )

    def test_3d_input(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 2)
        x = rand(ks[0], (2, 17, 128), jnp.float32)
        w = jnp.ones((128,), jnp.float32)
        out = rmsnorm(x, w, interpret=True)
        ref = rmsnorm_ref(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


class TestSelectiveScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,Din,N,chunk,block_d",
        [
            (2, 64, 32, 8, 16, 16),
            (1, 100, 48, 16, 32, 48),  # ragged seq
            (2, 128, 64, 4, 128, 32),  # single chunk
            (1, 96, 40, 8, 16, 64),  # block_d > Din
        ],
    )
    def test_matches_oracle(self, B, S, Din, N, chunk, block_d, dtype):
        ks = jax.random.split(jax.random.PRNGKey(5), 5)
        x = rand(ks[0], (B, S, Din), dtype)
        dt = jax.nn.softplus(rand(ks[1], (B, S, Din), jnp.float32))
        Bm = rand(ks[2], (B, S, N), jnp.float32)
        Cm = rand(ks[3], (B, S, N), jnp.float32)
        A = -jnp.exp(rand(ks[4], (Din, N), jnp.float32) * 0.5)
        y, h = selective_scan(
            x, dt, Bm, Cm, A, chunk=chunk, block_d=block_d, interpret=True
        )
        y_ref, h_ref = selective_scan_ref(x, dt, Bm, Cm, A)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            **(dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)),
        )
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-3, atol=1e-3)

    def test_initial_state_carried(self):
        """Scanning [x1; x2] == scan(x2, h0=scan(x1).h)."""
        ks = jax.random.split(jax.random.PRNGKey(9), 5)
        B, S, Din, N = 1, 64, 16, 4
        x = rand(ks[0], (B, S, Din), jnp.float32)
        dt = jax.nn.softplus(rand(ks[1], (B, S, Din), jnp.float32))
        Bm = rand(ks[2], (B, S, N), jnp.float32)
        Cm = rand(ks[3], (B, S, N), jnp.float32)
        A = -jnp.exp(rand(ks[4], (Din, N), jnp.float32) * 0.5)
        y_full, h_full = selective_scan(x, dt, Bm, Cm, A, chunk=16, interpret=True)
        half = S // 2
        _, h1 = selective_scan(
            x[:, :half], dt[:, :half], Bm[:, :half], Cm[:, :half], A,
            chunk=16, interpret=True,
        )
        y2, h2 = selective_scan(
            x[:, half:], dt[:, half:], Bm[:, half:], Cm[:, half:], A, h1,
            chunk=16, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(y2), np.asarray(y_full[:, half:]), rtol=1e-4, atol=1e-4
        )
