"""Decoder-only LM assembly: dense / MoE / SSM / hybrid / VLM-backbone.

One parameter template + three entry points per architecture:

* :func:`forward` — teacher-forcing logits (training);
* :func:`prefill` — forward + cache construction;
* :func:`decode_step` — one token against the cache.

**Layer plan.** Layers are grouped into *classes* by attention window
(full vs sliding). Each class stores its parameters stacked on a leading
axis and allocates its own decode cache: full-attention layers get a
``max_len`` KV cache, sliding-window layers get an O(window) ring buffer
— this is what makes hybrid archs (hymba: 29 SWA + 3 global layers)
feasible at 32k/500k contexts. Execution follows the original layer
order as a sequence of *runs*, each a ``lax.scan`` over a contiguous
slice of one class (uniform archs collapse to a single scan; the HLO
stays small for SPMD partitioning at 512 devices).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..distributed.sharding import logical
from .attention import (
    attention_block,
    attn_template,
    chunk_attention_block,
    paged_attention_block,
    paged_chunk_attention_block,
)
from .common import ModelConfig, ParamSpec
from .layers import (
    embed_template,
    gelu_mlp,
    mlp_template,
    rmsnorm,
    swiglu_mlp,
)
from .moe import moe_ffn, moe_template
from .ssm import mamba_block, mamba_decode_step, ssm_template

__all__ = [
    "lm_template",
    "forward",
    "prefill",
    "decode_step",
    "decode_step_paged",
    "prefill_chunk",
    "prefill_chunk_paged",
    "verify_step_paged",
    "supports_paged",
    "init_cache_shapes",
    "cache_logical_axes",
    "slot_cache_logical_axes",
    "layer_plan",
    "LayerPlan",
]

FULL_WINDOW = 2**30


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ClassSpec:
    window: int | None  # None = full attention
    layer_ids: tuple[int, ...]  # original layer indices, ascending

    @property
    def count(self) -> int:
        return len(self.layer_ids)


@dataclasses.dataclass(frozen=True)
class RunSpec:
    class_idx: int
    offset: int  # start within the class stack
    count: int


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    classes: tuple[ClassSpec, ...]
    runs: tuple[RunSpec, ...]


@functools.lru_cache(maxsize=None)
def layer_plan(cfg: ModelConfig) -> LayerPlan:
    windows = [cfg.window_for_layer(l) for l in range(cfg.n_layers)]
    uniq = sorted(set(windows), key=lambda w: (w is not None, w))
    by_window = {w: [] for w in uniq}
    for l, w in enumerate(windows):
        by_window[w].append(l)
    classes = tuple(ClassSpec(w, tuple(by_window[w])) for w in uniq)
    cls_of = {l: ci for ci, c in enumerate(classes) for l in c.layer_ids}
    pos_in_cls = {l: c.layer_ids.index(l) for c in classes for l in c.layer_ids}

    runs: list[RunSpec] = []
    l = 0
    while l < cfg.n_layers:
        ci = cls_of[l]
        start = pos_in_cls[l]
        n = 1
        while (
            l + n < cfg.n_layers
            and cls_of[l + n] == ci
            and pos_in_cls[l + n] == start + n
        ):
            n += 1
        runs.append(RunSpec(ci, start, n))
        l += n
    return LayerPlan(classes, tuple(runs))


def _class_layers_template(cfg: ModelConfig, n: int) -> dict:
    """Template for one class of ``n`` layers."""
    D = cfg.d_model
    layers: dict = {"ln1": ParamSpec((n, D), ("layers", "embed"), init="ones")}
    if cfg.block in ("attn", "hymba"):
        layers["attn"] = attn_template(cfg, n_layers=n)
        layers["ln2"] = ParamSpec((n, D), ("layers", "embed"), init="ones")
        if cfg.is_moe:
            layers["moe"] = moe_template(cfg, n_layers=n)
        else:
            layers["mlp"] = mlp_template(cfg, n_layers=n)
    if cfg.block in ("mamba", "hymba"):
        layers["ssm"] = ssm_template(cfg, n_layers=n)
    if cfg.block == "hymba":
        layers["norm_attn"] = ParamSpec((n, D), ("layers", "embed"), init="ones")
        layers["norm_ssm"] = ParamSpec((n, D), ("layers", "embed"), init="ones")
        layers["beta_attn"] = ParamSpec((n, D), ("layers", "embed"), init="ones")
        layers["beta_ssm"] = ParamSpec((n, D), ("layers", "embed"), init="ones")
    return layers


def lm_template(cfg: ModelConfig) -> dict:
    """Full parameter template for a decoder-only architecture."""
    cfg.validate()
    plan = layer_plan(cfg)
    t: dict = {
        "classes": {
            f"c{i}": _class_layers_template(cfg, c.count)
            for i, c in enumerate(plan.classes)
        },
    }
    emb = embed_template(cfg)
    keep_emb: dict = {}
    if cfg.stage_embed or (cfg.stage_unembed and cfg.tie_embeddings):
        keep_emb["tok"] = emb["tok"]
    if cfg.stage_unembed and not cfg.tie_embeddings:
        keep_emb["lm_head"] = emb["lm_head"]
    if keep_emb:
        t["embed"] = keep_emb
    if cfg.stage_unembed:
        t["final_norm"] = ParamSpec((cfg.d_model,), ("embed",), init="ones")
    if cfg.stage_embed and cfg.frontend == "patches":
        t["vision_proj"] = ParamSpec(
            (cfg.frontend_dim, cfg.d_model), ("frontend", "embed")
        )
    return t


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _embed(params, batch_or_tokens, cfg: ModelConfig, batch=None):
    """First-stage input: token embedding (+modality merge) — or, for a
    middle pipeline stage, the hidden states passed through verbatim."""
    dtype = cfg.compute_dtype
    if not cfg.stage_embed:
        hidden = batch["hidden"] if batch is not None else batch_or_tokens
        return logical(hidden.astype(dtype), ("batch", "act_seq", "embed"))
    tokens = batch_or_tokens
    x = params["embed"]["tok"].astype(dtype)[tokens]
    if cfg.frontend == "patches" and batch is not None and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(dtype)  # [B, P, frontend_dim]
        proj = jnp.einsum("bpf,fd->bpd", pe, params["vision_proj"].astype(dtype))
        P = proj.shape[1]
        x = jnp.concatenate([proj, x[:, P:]], axis=1)
    return logical(x, ("batch", "act_seq", "embed"))


def _unembed(params, x, cfg: ModelConfig):
    """Last-stage output: logits — or raw hidden states mid-pipeline."""
    if not cfg.stage_unembed:
        return x
    dtype = cfg.compute_dtype
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["tok"].astype(dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["embed"]["lm_head"].astype(dtype))
    return logical(logits, ("batch", "seq", "vocab"))


def _ffn(x, p_layer, cfg: ModelConfig):
    if cfg.is_moe:
        return moe_ffn(x, p_layer["moe"], cfg)
    if cfg.act == "swiglu":
        return swiglu_mlp(x, p_layer["mlp"], cfg.compute_dtype), {}
    return gelu_mlp(x, p_layer["mlp"], cfg.compute_dtype), {}


def _mixer(x_norm, p_layer, cfg: ModelConfig, *, positions, window, cache=None,
           window_static=None):
    """Token mixing by family. Returns (out, cache_parts dict)."""
    parts = {}
    if cfg.block in ("attn", "hymba"):
        if cache is None:
            kv = None
        elif "_write_idx" in cache:
            kv = (cache["k"], cache["v"], cache["_attn_len"], cache["_write_idx"])
        else:
            kv = (cache["k"], cache["v"], cache["_attn_len"])
        a_out, (k, v) = attention_block(
            x_norm, p_layer["attn"], cfg,
            positions=positions, window=window, cache=kv,
            window_static=window_static,
        )
        parts["k"], parts["v"] = k, v
        if cfg.block == "attn":
            return a_out, parts
    if cfg.block in ("mamba", "hymba"):
        if cache is None:
            m_out, (conv, ssm) = mamba_block(x_norm, p_layer["ssm"], cfg)
        else:
            m_out, (conv, ssm) = mamba_decode_step(
                x_norm, p_layer["ssm"], cfg, (cache["conv"], cache["ssm"])
            )
        parts["conv"], parts["ssm"] = conv, ssm
        if cfg.block == "mamba":
            return m_out, parts
    # hymba fusion: per-branch norm + learned gains, averaged.
    a_out = rmsnorm(a_out, p_layer["norm_attn"], cfg.rms_eps) * p_layer[
        "beta_attn"
    ].astype(a_out.dtype)
    m_out = rmsnorm(m_out, p_layer["norm_ssm"], cfg.rms_eps) * p_layer[
        "beta_ssm"
    ].astype(m_out.dtype)
    return 0.5 * (a_out + m_out), parts


def _layer_body(x, p_layer, cfg: ModelConfig, *, positions, window, cache=None,
                window_static=None):
    h = rmsnorm(x, p_layer["ln1"], cfg.rms_eps)
    mix, parts = _mixer(
        h, p_layer, cfg, positions=positions, window=window, cache=cache,
        window_static=window_static,
    )
    x = x + mix
    aux = {}
    if cfg.block in ("attn", "hymba"):
        h2 = rmsnorm(x, p_layer["ln2"], cfg.rms_eps)
        ff, aux = _ffn(h2, p_layer, cfg)
        x = x + ff
    return logical(x, ("batch", "act_seq", "embed")), parts, aux


def _slice_stack(tree, offset: int, count: int):
    return jax.tree_util.tree_map(lambda a: a[offset : offset + count], tree)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def forward(params, batch, cfg: ModelConfig):
    """Teacher-forcing logits. batch: {"tokens": [B,S], ...} -> [B,S,V]."""
    x_in = batch["tokens"] if cfg.stage_embed else batch["hidden"]
    S = x_in.shape[1]
    x = _embed(params, x_in, cfg, batch)
    positions = jnp.arange(S, dtype=jnp.int32)
    plan = layer_plan(cfg)

    lb_total = jnp.zeros((), jnp.float32)
    for run in plan.runs:
        cls = plan.classes[run.class_idx]
        window = jnp.int32(cls.window if cls.window is not None else FULL_WINDOW)
        p_run = _slice_stack(params["classes"][f"c{run.class_idx}"], run.offset, run.count)

        def body(x, p_layer, window=window, ws=cls.window):
            x, _, aux = _layer_body(
                x, p_layer, cfg, positions=positions, window=window,
                window_static=ws,
            )
            lb = aux.get("lb_loss", jnp.zeros((), jnp.float32))
            return x, lb

        kb = cfg.remat_block
        if cfg.remat and kb > 1 and run.count % kb == 0 and run.count > kb:
            # Block remat: one stored carry per kb layers; the inner scan
            # is recomputed during backward.
            p_blocked = jax.tree_util.tree_map(
                lambda a: a.reshape(run.count // kb, kb, *a.shape[1:]), p_run
            )

            @jax.checkpoint
            def block_body(x, p_chunk, body=body):
                x, lbs = jax.lax.scan(body, x, p_chunk)
                return x, jnp.sum(lbs)

            x, lbs = jax.lax.scan(block_body, x, p_blocked)
        else:
            if cfg.remat:
                body = jax.checkpoint(body)
            x, lbs = jax.lax.scan(body, x, p_run)
        lb_total = lb_total + jnp.sum(lbs)
    return _unembed(params, x, cfg), {"lb_loss": lb_total / max(cfg.n_layers, 1)}


def _class_cache_len(cls: ClassSpec, max_len: int) -> int:
    if cls.window is None:
        return max_len
    return min(max_len, cls.window)


def init_cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Abstract cache layout (ShapeDtypeStructs) for serve lowering."""
    plan = layer_plan(cfg)
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    dt = cfg.compute_dtype
    c: dict = {"len": jax.ShapeDtypeStruct((), jnp.int32)}
    for i, cls in enumerate(plan.classes):
        n = cls.count
        entry: dict = {}
        if cfg.block in ("attn", "hymba"):
            Lc = _class_cache_len(cls, max_len)
            entry["k"] = jax.ShapeDtypeStruct((n, batch, Lc, KV, Dh), dt)
            entry["v"] = jax.ShapeDtypeStruct((n, batch, Lc, KV, Dh), dt)
        if cfg.block in ("mamba", "hymba"):
            entry["conv"] = jax.ShapeDtypeStruct(
                (n, batch, cfg.ssm_conv - 1, cfg.d_inner), dt
            )
            entry["ssm"] = jax.ShapeDtypeStruct(
                (n, batch, cfg.d_inner, cfg.ssm_state), jnp.float32
            )
        c[f"c{i}"] = entry
    return c


def cache_logical_axes(cfg: ModelConfig) -> dict:
    plan = layer_plan(cfg)
    c: dict = {"len": ()}
    for i, _cls in enumerate(plan.classes):
        entry: dict = {}
        if cfg.block in ("attn", "hymba"):
            kv = ("layers", "cache_batch", "cache_seq", "kv_heads", "head_dim")
            entry["k"] = kv
            entry["v"] = kv
        if cfg.block in ("mamba", "hymba"):
            entry["conv"] = ("layers", "cache_batch", "conv", "ssm_inner")
            entry["ssm"] = ("layers", "cache_batch", "ssm_inner", "ssm_state")
        c[f"c{i}"] = entry
    return c


def slot_cache_logical_axes(cfg: ModelConfig) -> dict:
    """Axis names for the serving engine's slot-stacked decode cache.

    The engine stacks per-request caches (inner batch dim 1) on a new
    leading *slot* axis; that slot axis is the continuous-batching
    batch, so it takes the ``cache_batch`` name and the degenerate
    inner batch dim drops to None. The tree mirrors
    :func:`init_cache_shapes` leaf-for-leaf — the serving engine zips
    the two to commit each leaf to its replica submesh under
    :func:`repro.distributed.sharding.serve_cache_spec` (shards only
    ``cache_batch``; everything else replicates within the slice).
    """
    per_req = cache_logical_axes(cfg)
    out: dict = {"len": ("cache_batch",)}
    for key, entry in per_req.items():
        if key == "len":
            continue
        out[key] = {
            name: ("cache_batch",)
            + tuple(None if a == "cache_batch" else a for a in axes)
            for name, axes in entry.items()
        }
    return out


def prefill(params, batch, cfg: ModelConfig, *, max_len: int):
    """Forward over a prompt, building the decode cache.

    Full-attention classes keep the whole prompt's K/V (padded to
    ``max_len``); sliding-window classes keep an O(window) ring buffer of
    the last ``window`` positions.
    """
    x_in = batch["tokens"] if cfg.stage_embed else batch["hidden"]
    B, S = x_in.shape[:2]
    if max_len < S:
        raise ValueError("max_len must cover the prompt")
    x = _embed(params, x_in, cfg, batch)
    positions = jnp.arange(S, dtype=jnp.int32)
    plan = layer_plan(cfg)

    # Collect per-class stacked cache parts across runs.
    collected: dict[int, list] = {i: [] for i in range(len(plan.classes))}
    for run in plan.runs:
        cls = plan.classes[run.class_idx]
        window = jnp.int32(cls.window if cls.window is not None else FULL_WINDOW)
        p_run = _slice_stack(params["classes"][f"c{run.class_idx}"], run.offset, run.count)

        def body(x, p_layer, window=window, ws=cls.window):
            x, parts, _ = _layer_body(
                x, p_layer, cfg, positions=positions, window=window,
                window_static=ws,
            )
            return x, parts

        x, stacked = jax.lax.scan(body, x, p_run)
        collected[run.class_idx].append(stacked)

    # Last stage: logits for the final position; middle pipeline stages:
    # the full hidden sequence (the next stage prefills from it).
    logits = _unembed(params, x[:, -1:] if cfg.stage_unembed else x, cfg)

    cache: dict = {"len": jnp.int32(S)}
    for i, cls in enumerate(plan.classes):
        runs_parts = collected[i]
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *runs_parts
        )
        entry: dict = {}
        if "k" in stacked:
            Lc = _class_cache_len(cls, max_len)
            k, v = stacked["k"], stacked["v"]  # [n, B, S, KV, Dh]
            if cls.window is None or S <= Lc:
                pad = [(0, 0), (0, 0), (0, Lc - S), (0, 0), (0, 0)]
                entry["k"], entry["v"] = jnp.pad(k, pad), jnp.pad(v, pad)
            else:
                # Ring buffer of the last Lc positions: slot = pos % Lc.
                k_t, v_t = k[:, :, -Lc:], v[:, :, -Lc:]
                shift = S % Lc
                entry["k"] = jnp.roll(k_t, shift, axis=2)
                entry["v"] = jnp.roll(v_t, shift, axis=2)
            axes = cache_logical_axes(cfg)[f"c{i}"]
            entry["k"] = logical(entry["k"], axes["k"])
            entry["v"] = logical(entry["v"], axes["v"])
        if "conv" in stacked:
            entry["conv"] = stacked["conv"]
            entry["ssm"] = stacked["ssm"]
        cache[f"c{i}"] = entry
    return logits, cache


def decode_step(params, token, cache, cfg: ModelConfig):
    """One decode step. token: [B,1] -> (logits [B,1,V], updated cache).

    ``cache["len"]`` = number of tokens already in context (the new token
    gets position ``len`` and the cache grows to ``len + 1``).
    """
    x = _embed(params, token, cfg)
    positions = cache["len"][None].astype(jnp.int32)
    new_len = cache["len"] + 1
    plan = layer_plan(cfg)

    new_cache: dict = {"len": new_len}
    updated: dict[int, list] = {i: [] for i in range(len(plan.classes))}
    for run in plan.runs:
        cls = plan.classes[run.class_idx]
        p_run = _slice_stack(params["classes"][f"c{run.class_idx}"], run.offset, run.count)
        c_run = _slice_stack(
            {k: v for k, v in cache[f"c{run.class_idx}"].items()}, run.offset, run.count
        )
        if cls.window is None:
            # Plain cache: write at len, attend over new_len entries.
            attn_len = jnp.broadcast_to(new_len, (run.count,))
            window = jnp.broadcast_to(jnp.int32(FULL_WINDOW), (run.count,))
        else:
            Lc = None  # ring: length handled below
            ring = c_run.get("k")
            Lc = ring.shape[2] if ring is not None else cls.window
            # Write slot = len % Lc; valid entries = min(new_len, Lc).
            attn_len = jnp.broadcast_to(jnp.minimum(new_len, Lc), (run.count,))
            window = jnp.broadcast_to(jnp.int32(FULL_WINDOW), (run.count,))

        def body(x, scanned, cls=cls):
            p_layer, c_layer, a_len, win = scanned
            c_layer = dict(c_layer, _attn_len=a_len)
            if cls.window is not None and "k" in c_layer:
                Lc_ = c_layer["k"].shape[1]
                slot = jnp.mod(positions[0], Lc_)
                if cfg.ring_impl == "index":
                    # Direct slot addressing: write at len % Lc; all valid
                    # entries are in-window by ring construction.
                    c_layer["_write_idx"] = slot
                    return _layer_body(
                        x, p_layer, cfg,
                        positions=positions, window=win, cache=c_layer,
                    )[:2]
                # Baseline "roll": rotate so the write (at _attn_len - 1)
                # lands on slot len % Lc, then rotate back.
                tgt = a_len - 1
                shift = tgt - slot
                c_layer["k"] = jnp.roll(c_layer["k"], shift, axis=1)
                c_layer["v"] = jnp.roll(c_layer["v"], shift, axis=1)
                x, parts, _ = _layer_body(
                    x, p_layer, cfg, positions=positions, window=win, cache=c_layer
                )
                if "k" in parts:
                    parts["k"] = jnp.roll(parts["k"], -shift, axis=1)
                    parts["v"] = jnp.roll(parts["v"], -shift, axis=1)
                return x, parts
            x, parts, _ = _layer_body(
                x, p_layer, cfg, positions=positions, window=win, cache=c_layer
            )
            return x, parts

        x, stacked = jax.lax.scan(body, x, (p_run, c_run, attn_len, window))
        updated[run.class_idx].append(stacked)

    logits = _unembed(params, x, cfg)
    for i in range(len(plan.classes)):
        new_cache[f"c{i}"] = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *updated[i]
        )
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged decode
# ---------------------------------------------------------------------------

def supports_paged(cfg: ModelConfig) -> bool:
    """Paged serving pages the *unbounded* full-attention KV. It covers
    every pure-attention architecture (dense, GQA, MoE, VLM backbone)
    whose layers all attend globally; sliding-window ring buffers and
    SSM states are already O(window)/O(1) and keep the dense slot
    layout, so hybrid/mamba archs serve dense."""
    if cfg.is_encdec or cfg.block != "attn":
        return False
    plan = layer_plan(cfg)
    return len(plan.classes) == 1 and plan.classes[0].window is None


def decode_step_paged(
    params,
    token,
    pools: dict,
    lengths,
    block_tables,
    cfg: ModelConfig,
):
    """One decode step for a whole slot batch against a shared page pool.

    Unlike :func:`decode_step` (per-request cache, vmapped by the
    engine), the paged step is natively batched: the W requests share
    the replica's page pool and cannot be vmapped over it (each lane
    scatters into the common arrays). Per-request state is ``lengths``
    [W] (tokens already in context; ``-1`` marks a masked lane, which
    reads/writes only the scratch page) and ``block_tables`` [W, NB] —
    write coordinates are derived in-graph.

    token: [W, 1] ids (stage 0) or hidden [W, 1, D] (later stages);
    pools: {"k": [n_layers, P+1, page, KV * Dh], "v": ...} page rows, as
    the serving engine keeps them (or [n_layers, P+1, page, KV, Dh], the
    same bytes) — int8 pools additionally carry {"k_scale", "v_scale":
    [n_layers, P+1, page]} per-row fp32 scales (quantized at scatter,
    dequantized in the page gather). The layer loop carries the pool
    stack: layer ``l`` scatters its rows in place at ``[l, page, offset]``
    and attention reads layer ``l`` of the stack by index, so no layer of
    the pool is sliced out or copied back, and a caller that donates
    ``pools`` gets them updated in place. Returns (logits/hidden
    [W, 1, V|D], updated pools).
    """
    if not supports_paged(cfg):
        raise ValueError(f"{cfg.name}: paged decode needs uniform full attention")
    x = _embed(params, token, cfg)
    lengths = jnp.asarray(lengths, jnp.int32)
    active = lengths >= 0
    pos = jnp.maximum(lengths, 0)
    positions = pos[:, None]  # [W, 1]
    # Write coordinates are layer-invariant: derive them once here, not
    # inside the layer scan. Masked lanes go to the scratch page.
    W = pos.shape[0]
    page = pools["k"].shape[2]
    scratch = pools["k"].shape[1] - 1
    write_pages = jnp.where(
        active, block_tables[jnp.arange(W), pos // page], scratch
    )
    write_offs = pos % page
    p_run = params["classes"]["c0"]

    def body(carry, scanned):
        x, pools = carry
        p_layer, layer = scanned
        h = rmsnorm(x, p_layer["ln1"], cfg.rms_eps)
        a, pools = paged_attention_block(
            h, p_layer["attn"], cfg,
            positions=positions, pages=pools, layer=layer,
            block_tables=block_tables,
            write_pages=write_pages, write_offs=write_offs,
        )
        x = x + a
        h2 = rmsnorm(x, p_layer["ln2"], cfg.rms_eps)
        ff, _ = _ffn(h2, p_layer, cfg)
        return (x + ff, pools), None

    (x, pools), _ = jax.lax.scan(body, (x, pools), (p_run, _layer_ids(pools)))
    return _unembed(params, x, cfg), pools


def _layer_ids(pools: dict) -> jax.Array:
    """The layer index of each step of a paged layer loop."""
    return jnp.arange(pools["k"].shape[0], dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------

def prefill_chunk(params, chunk, cache, offset, valid, cfg: ModelConfig):
    """Advance one request's dense cache by a fixed-width prompt chunk.

    The Sarathi-style middle ground between :func:`prefill` (whole
    prompt, one shape per length) and :func:`decode_step` (one token):
    ``C = chunk width`` tokens join an existing cache at absolute
    positions ``offset .. offset + C - 1``. ``valid <= C`` of them are
    real — the padding tail's K/V writes are dropped or overwritten by
    the next chunk, and its outputs are garbage the engine discards —
    so every chunk of every prompt shares one compiled shape.

    Uniform full-attention architectures only (the same coverage as
    :func:`supports_paged`): ring buffers and SSM states advance
    token-by-token and keep whole-prompt prefill.

    chunk: {"tokens": [B, C]} (stage 0) or {"hidden": [B, C, D]};
    offset, valid: int32 scalars (per-lane under the engine's vmap).
    Returns ([B, C, V|D] per-position outputs, updated cache with
    ``len = offset + valid``).
    """
    if not supports_paged(cfg):
        raise ValueError(
            f"{cfg.name}: chunked prefill needs uniform full attention"
        )
    x_in = chunk["tokens"] if cfg.stage_embed else chunk["hidden"]
    x = _embed(params, x_in, cfg, chunk)
    offset = jnp.asarray(offset, jnp.int32)
    p_run = params["classes"]["c0"]
    c0 = cache["c0"]

    def body(x, scanned):
        p_layer, k_cache, v_cache = scanned
        h = rmsnorm(x, p_layer["ln1"], cfg.rms_eps)
        a, (k_cache, v_cache) = chunk_attention_block(
            h, p_layer["attn"], cfg,
            offset=offset, k_cache=k_cache, v_cache=v_cache,
        )
        x = x + a
        h2 = rmsnorm(x, p_layer["ln2"], cfg.rms_eps)
        ff, _ = _ffn(h2, p_layer, cfg)
        return x + ff, (k_cache, v_cache)

    x, (k, v) = jax.lax.scan(body, x, (p_run, c0["k"], c0["v"]))
    new_cache = {
        "len": (offset + jnp.asarray(valid, jnp.int32)).astype(cache["len"].dtype),
        "c0": {"k": k, "v": v},
    }
    return _unembed(params, x, cfg), new_cache


def prefill_chunk_paged(
    params, chunk, pools: dict, offsets, valids, block_tables, cfg: ModelConfig
):
    """Advance a whole slot batch's paged caches by one prompt chunk.

    The paged sibling of :func:`prefill_chunk`, natively batched like
    :func:`decode_step_paged` (the W lanes share the replica's page
    pool): each lane's chunk K/V are scattered incrementally into its
    reserved pages — write coordinates come from the block table, masked
    lanes (``offsets == -1``) and padding positions (``>= valids``) land
    on the scratch page — and the chunk attends over the paged prefix
    through the paged kernel, or off the Pallas path its gather fallback
    (:mod:`repro.kernels.decode_attention`).

    chunk: [W, C] ids (stage 0) or [W, C, D] hidden; offsets [W] int32
    (tokens already in context; -1 = masked lane); valids [W] int32;
    pools: {"k": [n_layers, P+1, page, KV * Dh], "v": ...} (see
    :func:`decode_step_paged`) — int8 pools additionally carry per-row
    fp32 scales.
    The layer loop carries the pool stack and each layer writes and reads
    its own layer of it in place, as in :func:`decode_step_paged`.
    Returns ([W, C, V|D] per-position outputs, updated pools).
    """
    if not supports_paged(cfg):
        raise ValueError(
            f"{cfg.name}: chunked prefill needs uniform full attention"
        )
    x = _embed(params, chunk, cfg)
    offsets = jnp.asarray(offsets, jnp.int32)
    valids = jnp.asarray(valids, jnp.int32)
    active = offsets >= 0
    pos0 = jnp.maximum(offsets, 0)
    W, C = x.shape[:2]
    positions = pos0[:, None] + jnp.arange(C, dtype=jnp.int32)  # [W, C]
    # Write coordinates are layer-invariant: derive them once here, not
    # inside the layer scan. Only real chunk tokens of active lanes
    # touch reserved pages; everything else goes to the scratch page.
    page = pools["k"].shape[2]
    scratch = pools["k"].shape[1] - 1
    writable = active[:, None] & (jnp.arange(C)[None, :] < valids[:, None])
    rows = jnp.arange(W, dtype=jnp.int32)[:, None]
    table_pages = block_tables[rows, jnp.minimum(positions // page,
                                                 block_tables.shape[1] - 1)]
    write_pages = jnp.where(writable, table_pages, scratch)
    write_offs = positions % page
    p_run = params["classes"]["c0"]

    def body(carry, scanned):
        x, pools = carry
        p_layer, layer = scanned
        h = rmsnorm(x, p_layer["ln1"], cfg.rms_eps)
        a, pools = paged_chunk_attention_block(
            h, p_layer["attn"], cfg,
            positions=positions, pages=pools, layer=layer,
            block_tables=block_tables,
            write_pages=write_pages, write_offs=write_offs,
        )
        x = x + a
        h2 = rmsnorm(x, p_layer["ln2"], cfg.rms_eps)
        ff, _ = _ffn(h2, p_layer, cfg)
        return (x + ff, pools), None

    (x, pools), _ = jax.lax.scan(body, (x, pools), (p_run, _layer_ids(pools)))
    return _unembed(params, x, cfg), pools


def verify_step_paged(
    params, chunk, pools: dict, offsets, valids, block_tables, cfg: ModelConfig
):
    """Verify ``k + 1`` speculative positions in one paged chunk call.

    Draft-verify decoding's target step: the chunk call shape (C query
    positions against a growing paged prefix, causal-masked in-kernel
    with LSE merge) *is* the verification step, so this delegates to
    :func:`prefill_chunk_paged` — no new kernel. Lane ``w`` carries
    ``[last_committed_token, d_1 .. d_k]`` at absolute positions
    ``offsets[w] .. offsets[w] + k``; position ``j``'s output row is the
    logits the sequential decode path would have produced after
    consuming the first ``j + 1`` of those inputs, **bit-for-bit**
    (per-row softmax/matmul reductions are independent of the other
    rows, and the scattered K/V page rows are byte-identical to the ones
    :func:`decode_step_paged` writes — ``tests/test_spec_decode.py``
    asserts both), which is what makes greedy accept/reject exact:
    accepting the longest prefix with ``d_j == argmax(row[j - 1])`` and
    rewinding the rest reproduces plain decode's token stream exactly.

    Same signature and coverage as :func:`prefill_chunk_paged`
    (``supports_paged`` families; ``valids[w] - 1`` drafts per lane,
    ``offsets[w] == -1`` masks a lane; int8 pools quantize rows at
    scatter, so rewound-and-rewritten rows stay exact). Returns
    ``([W, C, V|D] per-position outputs, updated pools)``.
    """
    return prefill_chunk_paged(
        params, chunk, pools, offsets, valids, block_tables, cfg
    )
