"""The Pallas kernels of the main path compile for a TPU v5e.

Interpret mode (every other kernel test) accepts block shapes the TPU
compiler refuses, so these tests compile each kernel for a described
``v5e:2x2`` topology — no chip attached — at the widths it serves:
stablelm-1.6b (32 MHA heads of 64, d_model 2048) for attention and
rmsnorm, falcon-mamba-7b (d_inner 8192, state 16) for the selective scan.
Each asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``), not an XLA fallback. One serving stage's paged
decode and chunk programs are compiled whole, at the benchmark cell's
sizes, to show that they update the donated KV pool in place.

The topology is described only inside the module fixture: loading the
TPU library is a per-process lock, and the test workers import every
test file.
"""

import functools
import re

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


# One stage of the benchmark cell: stablelm-1.6b in 3 stages of 8 layers,
# 1,024 pages of 16 (+ the scratch page), 8 lanes, 256-token chunks.
STAGE_PAGES, STAGE_PAGE, STAGE_W, STAGE_NB = 1025, 16, 8, 256


def _stage_program(kind, kv_dtype, sharding):
    """The compiled decode or chunk program of a middle stage, its pool
    donated, as the serving engine dispatches it."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.common import abstract_params
    from repro.serving.partition import stage_configs

    full = dataclasses.replace(
        get_config("stablelm-1.6b"), dtype="bfloat16", param_dtype="bfloat16",
        attn_impl="pallas",
    )
    cfg = stage_configs(full, 3)[1]
    model = build_model(cfg)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=sharding)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), abstract_params(model.template, cfg.param_dtype)
    )
    shape = (cfg.n_layers, STAGE_PAGES, STAGE_PAGE, cfg.n_kv_heads * cfg.head_dim)
    pools = {"k": sds(shape, kv_dtype), "v": sds(shape, kv_dtype)}
    if kv_dtype == "int8":
        pools["k_scale"] = sds(shape[:3], jnp.float32)
        pools["v_scale"] = sds(shape[:3], jnp.float32)
    lanes = sds((STAGE_W,), jnp.int32)
    bt = sds((STAGE_W, STAGE_NB), jnp.int32)
    if kind == "decode":
        fn = jax.jit(model.decode_paged, donate_argnums=(2,))
        args = (params, sds((STAGE_W, 1, cfg.d_model), cfg.dtype), pools, lanes, bt)
    else:
        fn = jax.jit(model.prefill_chunk_paged, donate_argnums=(2,))
        x = sds((STAGE_W, CHUNK, cfg.d_model), cfg.dtype)
        args = (params, x, pools, lanes, lanes, bt)
    pool_shapes = {
        shape,
        shape[1:],
        shape[:3] + (cfg.n_kv_heads, cfg.head_dim),
        shape[1:3] + (cfg.n_kv_heads, cfg.head_dim),
    }
    return fn.lower(*args).compile(), pool_shapes, pools["k"]


def _pool_moves(text, pool_shapes):
    """Copies, slices and slice updates in the optimized HLO (fusion
    bodies included) whose result is a pool or one layer of it."""
    hits = []
    op = re.compile(r"= \w+\[([0-9,]+)\](?:\{[^}]*\})? ([\w-]+)\(")
    for line in text.splitlines():
        m = op.search(line)
        if not m or m.group(2) not in (
            "copy", "copy-start", "dynamic-slice", "dynamic-update-slice"
        ):
            continue
        dims = tuple(int(d) for d in m.group(1).split(","))
        while dims and dims[0] == 1:
            dims = dims[1:]
        if dims in pool_shapes:
            hits.append(line.strip())
    return hits


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_stage_program_updates_the_pool_in_place(one_chip, monkeypatch, kind, kv_dtype):
    """The layer loop carries the pool stack: no copy, slice or slice
    update of a pool or of one of its layers is left in the program, the
    Mosaic kernel reads the stack, and the program's temporaries stay
    below one pool (the scan over pool layers needed about two pools)."""
    import repro.models.attention as attention

    # Off the chip the model runs its kernels in interpret mode; this
    # compile is for the chip, where they run as Mosaic kernels.
    monkeypatch.setattr(attention, "_use_interpret", lambda: False)
    compiled, pool_shapes, pool = _stage_program(kind, kv_dtype, one_chip)
    text = compiled.as_text()
    assert _pool_moves(text, pool_shapes) == []
    assert "tpu_custom_call" in text
    pool_bytes = pool.size * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
