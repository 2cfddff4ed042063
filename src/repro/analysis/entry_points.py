"""Lint entry points: the serving surface as traceable jaxprs.

Out of the box the lint covers, for every ``supports_paged`` registry
model (smoke config, real compute dtype): ``prefill_batch`` /
``decode_batch`` (the dense continuous-batching paths),
``prefill_chunk_batch`` (dense chunked prefill), ``decode_step_paged``,
``prefill_chunk_paged`` and ``verify_step_paged`` (the speculative
draft-verify chunk, traced at the default ``spec_k``) in both
``attn_impl`` variants (``xla`` gather fallback vs ``pallas`` kernels)
plus an int8-pool variant, and
the dense paths of every non-paged LM family. The two Pallas paged
kernels are also traced standalone (``kernel:*``) so the zero-gather
budget binds at the kernel boundary, not just through the model.

Entry-point names are ``model:kind:variant`` (e.g.
``stablelm-1.6b:decode_step_paged:pallas``) — the glob keys of
``budgets.json`` resolve against them. Tracing is lazy and abstract
(``jax.make_jaxpr`` over ``ShapeDtypeStruct`` params), so building the
full matrix never allocates model weights.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr

from ..configs import ARCH_NAMES, get_smoke_config
from ..kernels.decode_attention import PALLAS_PAGED_KERNELS
from ..models import build_model
from ..models.common import abstract_params
from ..models.transformer import supports_paged

__all__ = ["EntryPoint", "build_entry_points", "paged_model_names"]

# Trace shapes: tiny but structurally faithful (W slot lanes, C-token
# chunks, an NB-block table over a P-page pool plus the scratch page).
_W, _C, _S, _N, _MAX_LEN = 4, 8, 8, 2, 64
_PAGE, _NB, _P = 16, 4, 16
# Speculative verify traces at the engine's default spec_k: the chunk
# carries [last_token, d_1..d_k] = k + 1 positions per lane.
_SPEC_K = 4


@dataclasses.dataclass
class EntryPoint:
    """One lintable entry point; ``jaxpr`` traces lazily and caches.

    ``tokens`` is the number of tokens one invocation advances (the
    denominator of the memory pass's ``bytes_per_token``);
    ``kv_pool_bytes`` / ``kv_pool_bytes_fp32`` carry the paged KV pool
    footprint at the traced dtype and its fp32 equivalent, so the
    ``kv-page-ratio`` rule can enforce the int8 reduction
    dtype-normalized (smoke configs trace bf16 pools)."""

    name: str  # "model:kind:variant"
    model: str
    kind: str
    variant: str
    _make: Callable[[], ClosedJaxpr]
    _jaxpr: ClosedJaxpr | None = None
    tokens: int = 1
    kv_pool_bytes: int | None = None
    kv_pool_bytes_fp32: int | None = None
    _memory: object = None  # MemoryStats cache (see analysis.memory)

    @property
    def jaxpr(self) -> ClosedJaxpr:
        if self._jaxpr is None:
            self._jaxpr = self._make()
        return self._jaxpr


def paged_model_names() -> list[str]:
    """Registry models the paged serving paths cover."""
    out = []
    for name in ARCH_NAMES:
        cfg = get_smoke_config(name)
        if not cfg.is_encdec and supports_paged(cfg):
            out.append(name)
    return out


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _pool_sds(cfg, kv_dtype):
    # The serving engine's pools: page rows, a position's heads side by side.
    shape = (cfg.n_layers, _P + 1, _PAGE, cfg.n_kv_heads * cfg.head_dim)
    pools = {"k": _sds(shape, kv_dtype), "v": _sds(shape, kv_dtype)}
    if jnp.dtype(kv_dtype) == jnp.int8:
        pools["k_scale"] = _sds(shape[:3], jnp.float32)
        pools["v_scale"] = _sds(shape[:3], jnp.float32)
    return pools


def _pool_bytes(cfg, kv_dtype) -> int:
    """Total paged KV pool footprint at the trace shapes (k + v pools,
    plus per-row fp32 scales for int8)."""
    rows = cfg.n_layers * (_P + 1) * _PAGE
    data = 2 * rows * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(kv_dtype).itemsize
    scales = 2 * rows * 4 if jnp.dtype(kv_dtype) == jnp.int8 else 0
    return data + scales


def _stacked_cache_sds(model, n: int):
    shapes = model.cache_shapes(1, _MAX_LEN)
    return jax.tree_util.tree_map(
        lambda s: _sds((n,) + tuple(s.shape), s.dtype), shapes
    )


def _model_entries(name: str) -> list[EntryPoint]:
    cfg = get_smoke_config(name)
    if cfg.is_encdec:
        # The engine's submit() path carries decoder-only token streams;
        # encoder-decoder serving is out of the lint's scope for now.
        return []
    entries: list[EntryPoint] = []

    def add(kind: str, variant: str, make, tokens: int = 1, **meta):
        entries.append(
            EntryPoint(
                f"{name}:{kind}:{variant}", name, kind, variant, make,
                tokens=tokens, **meta,
            )
        )

    def dense_model():
        return build_model(cfg)

    def make_prefill_batch():
        model = dense_model()
        params = abstract_params(model.template, cfg.param_dtype)
        batch = {"tokens": _sds((_N, 1, _S), jnp.int32)}
        return jax.make_jaxpr(
            lambda p, b: model.prefill_batch(p, b, _MAX_LEN)
        )(params, batch)

    def make_decode_batch():
        model = dense_model()
        params = abstract_params(model.template, cfg.param_dtype)
        tok = _sds((_N, 1, 1), jnp.int32)
        caches = _stacked_cache_sds(model, _N)
        return jax.make_jaxpr(model.decode_batch)(params, tok, caches)

    add("prefill_batch", "dense", make_prefill_batch, tokens=_N * _S)
    add("decode_batch", "dense", make_decode_batch, tokens=_N)
    if not supports_paged(cfg):
        return entries

    def make_prefill_chunk_batch():
        model = dense_model()
        params = abstract_params(model.template, cfg.param_dtype)
        chunk = {"tokens": _sds((_N, 1, _C), jnp.int32)}
        caches = _stacked_cache_sds(model, _N)
        offs = _sds((_N,), jnp.int32)
        valids = _sds((_N,), jnp.int32)
        return jax.make_jaxpr(model.prefill_chunk_batch)(
            params, chunk, caches, offs, valids
        )

    add("prefill_chunk_batch", "dense", make_prefill_chunk_batch,
        tokens=_N * _C)

    for impl in ("xla", "pallas"):
        kv_dtypes = [cfg.dtype] if impl == "xla" else [cfg.dtype, "int8"]
        for kv_dtype in kv_dtypes:
            variant = impl if kv_dtype != "int8" else f"{impl}-int8"
            cfg_v = dataclasses.replace(cfg, attn_impl=impl)

            def make_decode_paged(cfg_v=cfg_v, kv_dtype=kv_dtype):
                model = build_model(cfg_v)
                params = abstract_params(model.template, cfg_v.param_dtype)
                tok = _sds((_W, 1), jnp.int32)
                pools = _pool_sds(cfg_v, kv_dtype)
                lens = _sds((_W,), jnp.int32)
                bt = _sds((_W, _NB), jnp.int32)
                return jax.make_jaxpr(model.decode_paged)(
                    params, tok, pools, lens, bt
                )

            def make_chunk_paged(cfg_v=cfg_v, kv_dtype=kv_dtype):
                model = build_model(cfg_v)
                params = abstract_params(model.template, cfg_v.param_dtype)
                chunk = _sds((_W, _C), jnp.int32)
                pools = _pool_sds(cfg_v, kv_dtype)
                offs = _sds((_W,), jnp.int32)
                valids = _sds((_W,), jnp.int32)
                bt = _sds((_W, _NB), jnp.int32)
                return jax.make_jaxpr(model.prefill_chunk_paged)(
                    params, chunk, pools, offs, valids, bt
                )

            def make_verify_paged(cfg_v=cfg_v, kv_dtype=kv_dtype):
                model = build_model(cfg_v)
                params = abstract_params(model.template, cfg_v.param_dtype)
                chunk = _sds((_W, _SPEC_K + 1), jnp.int32)
                pools = _pool_sds(cfg_v, kv_dtype)
                offs = _sds((_W,), jnp.int32)
                valids = _sds((_W,), jnp.int32)
                bt = _sds((_W, _NB), jnp.int32)
                return jax.make_jaxpr(model.verify_step_paged)(
                    params, chunk, pools, offs, valids, bt
                )

            pool_meta = dict(
                kv_pool_bytes=_pool_bytes(cfg_v, kv_dtype),
                kv_pool_bytes_fp32=_pool_bytes(cfg_v, jnp.float32),
            )
            add("decode_step_paged", variant, make_decode_paged,
                tokens=_W, **pool_meta)
            add("prefill_chunk_paged", variant, make_chunk_paged,
                tokens=_W * _C, **pool_meta)
            add("verify_step_paged", variant, make_verify_paged,
                tokens=_W * (_SPEC_K + 1), **pool_meta)
    return entries


def _kernel_entries() -> list[EntryPoint]:
    """The Pallas paged kernels traced standalone, as serving calls them
    (one layer of a page-row pool stack): the zero-gather budget binds
    directly at the kernel boundary."""
    B, KV, G, D = 2, 2, 2, 8
    page, NB, C, L = 8, 3, 4, 2
    P = B * NB + 1
    entries: list[EntryPoint] = []
    for kernel_name, fn in PALLAS_PAGED_KERNELS.items():
        prefill = "prefill" in kernel_name

        def make(fn=fn, prefill=prefill):
            q_shape = (B, C, KV * G, D) if prefill else (B, 1, KV * G, D)
            q = _sds(q_shape, jnp.float32)
            k = _sds((L, P, page, KV * D), jnp.float32)
            v = _sds((L, P, page, KV * D), jnp.float32)
            bt = _sds((B, NB), jnp.int32)
            idx = _sds((B,), jnp.int32)  # lengths (decode) / offsets (prefill)
            layer = _sds((), jnp.int32)
            return jax.make_jaxpr(
                lambda q, k, v, bt, idx, li: fn(q, k, v, bt, idx, layer=li)
            )(q, k, v, bt, idx, layer)

        entries.append(
            EntryPoint(f"kernel:{kernel_name}:pallas", "kernel", kernel_name,
                       "pallas", make, tokens=B * C if prefill else B)
        )
    return entries


def build_entry_points(
    models: list[str] | None = None, include_kernels: bool = True
) -> list[EntryPoint]:
    """The full lint matrix (lazily traced). ``models`` filters by
    registry name; kernels ride along unless disabled."""
    entries: list[EntryPoint] = []
    for name in models if models is not None else ARCH_NAMES:
        entries.extend(_model_entries(name))
    if include_kernels:
        entries.extend(_kernel_entries())
    return entries
