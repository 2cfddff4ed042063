"""The Pallas kernels of the main path compile for a TPU v5e.

Interpret mode (every other kernel test) accepts block shapes the TPU
compiler refuses, so these tests compile each kernel for a described
``v5e:2x2`` topology — no chip attached — at the widths it serves:
stablelm-1.6b (32 MHA heads of 64, d_model 2048) for attention and
rmsnorm, falcon-mamba-7b (d_inner 8192, state 16) for the selective scan.
Each asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``), not an XLA fallback.

The topology is described only inside the module fixture: loading the
TPU library is a per-process lock, and the test workers import every
test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (
    decode_attention,
    paged_decode_attention,
    paged_prefill_attention_pallas,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.selective_scan import selective_scan

B, H, KV, D, S = 4, 32, 32, 64, 2048  # stablelm-1.6b, max_len 2048
D_MODEL = 2048
CHUNK = 256  # chunked-prefill width
MAMBA_DIN, MAMBA_N, MAMBA_CHUNK = 8192, 16, 256  # falcon-mamba-7b


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pages(page):
    return B * S // page + 1, S // page  # pool pages (+ scratch), row width


def test_flash_attention(one_chip):
    text = _compiled_text(
        functools.partial(flash_attention, causal=True),
        one_chip,
        ((1, 1024, H, D), jnp.bfloat16),
        ((1, 1024, KV, D), jnp.bfloat16),
        ((1, 1024, KV, D), jnp.bfloat16),
    )
    assert "tpu_custom_call" in text


def test_dense_decode(one_chip):
    text = _compiled_text(
        decode_attention,
        one_chip,
        ((B, 1, H, D), jnp.bfloat16),
        ((B, S, KV, D), jnp.bfloat16),
        ((B, S, KV, D), jnp.bfloat16),
        ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page", [16, 32])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode(one_chip, page, kv_dtype):
    P, NB = _pages(page)
    shapes = [
        ((B, 1, H, D), jnp.bfloat16),
        ((P, page, KV, D), jnp.dtype(kv_dtype)),
        ((P, page, KV, D), jnp.dtype(kv_dtype)),
        ((B, NB), jnp.int32),
        ((B,), jnp.int32),
    ]
    if kv_dtype == "int8":
        shapes += [((P, page), jnp.float32)] * 2

        def fn(q, k, v, bt, n, ks, vs):
            return paged_decode_attention(q, k, v, bt, n, k_scales=ks, v_scales=vs)
    else:
        fn = paged_decode_attention
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_prefill(one_chip, kv_dtype):
    P, NB = _pages(16)
    shapes = [
        ((B, CHUNK, H, D), jnp.bfloat16),
        ((P, 16, KV, D), jnp.dtype(kv_dtype)),
        ((P, 16, KV, D), jnp.dtype(kv_dtype)),
        ((B, NB), jnp.int32),
        ((B,), jnp.int32),
    ]
    if kv_dtype == "int8":
        shapes += [((P, 16), jnp.float32)] * 2

        def fn(q, k, v, bt, off, ks, vs):
            return paged_prefill_attention_pallas(
                q, k, v, bt, off, k_scales=ks, v_scales=vs
            )
    else:
        fn = paged_prefill_attention_pallas
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_rmsnorm(one_chip):
    text = _compiled_text(
        rmsnorm,
        one_chip,
        ((B * 256, D_MODEL), jnp.bfloat16),
        ((D_MODEL,), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_selective_scan(one_chip):
    text = _compiled_text(
        functools.partial(selective_scan, chunk=MAMBA_CHUNK),
        one_chip,
        ((1, 1024, MAMBA_DIN), jnp.float32),
        ((1, 1024, MAMBA_DIN), jnp.float32),
        ((1, 1024, MAMBA_N), jnp.float32),
        ((1, 1024, MAMBA_N), jnp.float32),
        ((MAMBA_DIN, MAMBA_N), jnp.float32),
    )
    assert "tpu_custom_call" in text
