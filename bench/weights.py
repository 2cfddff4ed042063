"""Weights from a seed, in the layout the serving program takes.

Each leaf of each layer is drawn from its own key, ``fold_in`` of the
seed's key with the layer and the leaf, so one layer can be made again
alone: the reference does that, layer by layer, and never reads what the
program holds. The program gets the weights of a pipeline stage as one
stacked array per leaf, made on the device in one jitted call in the type
they are served in.

The layout is that of a uniform pre-norm decoder: RMSNorm, attention with
``wq [D, H, Dh]``, ``wk``/``wv [D, KV, Dh]``, ``wo [H, Dh, D]``, RMSNorm,
SwiGLU ``wi_gate``/``wi_up [D, F]``, ``wo [F, D]``; the token table
``[V, D]``, a final RMSNorm and, when not tied, ``lm_head [D, V]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.work import Widths

__all__ = ["LAYER_LEAVES", "layer_weights", "top_weights", "stage_ranges", "served_params"]

# A layer's leaves as (group, leaf); the group "" is the layer itself.
LAYER_LEAVES = (
    ("", "ln1"), ("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
    ("", "ln2"), ("mlp", "wi_gate"), ("mlp", "wi_up"), ("mlp", "wo"),
)


def _leaf_shape_std(w: Widths, group: str, leaf: str):
    D, H, KV, Dh, F = w.d_model, w.n_heads, w.n_kv_heads, w.head_dim, w.d_ff
    return {
        ("", "ln1"): ((D,), None),
        ("", "ln2"): ((D,), None),
        ("attn", "wq"): ((D, H, Dh), D**-0.5),
        ("attn", "wk"): ((D, KV, Dh), D**-0.5),
        ("attn", "wv"): ((D, KV, Dh), D**-0.5),
        ("attn", "wo"): ((H, Dh, D), (H * Dh) ** -0.5),
        ("mlp", "wi_gate"): ((D, F), D**-0.5),
        ("mlp", "wi_up"): ((D, F), D**-0.5),
        ("mlp", "wo"): ((F, D), F**-0.5),
    }[(group, leaf)]


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed, wider than 32 bits too."""
    seed = int(seed) & (2**64 - 1)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _draw(key, shape, std, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    # Norm gains sit near 1 but not at it, so the check sees them applied.
    x = 1.0 + 0.1 * x if std is None else x * std
    return x.astype(dtype)


def layer_weights(w: Widths, key, layer, dtype=jnp.bfloat16) -> dict:
    """One layer's leaves as ``{"ln1", "attn": {...}, "ln2", "mlp": {...}}``."""
    k_layer = jax.random.fold_in(key, layer)
    out: dict = {"attn": {}, "mlp": {}}
    for i, (group, leaf) in enumerate(LAYER_LEAVES):
        shape, std = _leaf_shape_std(w, group, leaf)
        x = _draw(jax.random.fold_in(k_layer, i), shape, std, dtype)
        if group:
            out[group][leaf] = x
        else:
            out[leaf] = x
    return out


def top_weights(w: Widths, key, tied: bool, dtype=jnp.bfloat16) -> dict:
    """Token table, final norm gain and (untied) output head."""
    k_top = jax.random.fold_in(key, 1 << 20)
    D, V = w.d_model, w.vocab
    out = {
        "tok": _draw(jax.random.fold_in(k_top, 0), (V, D), D**-0.5, dtype),
        "final_norm": _draw(jax.random.fold_in(k_top, 1), (D,), None, dtype),
    }
    if not tied:
        out["lm_head"] = _draw(jax.random.fold_in(k_top, 2), (D, V), D**-0.5, dtype)
    return out


def stage_ranges(n_layers: int, n_stages: int) -> list[tuple[int, int]]:
    """Contiguous layer ranges, the first ``n_layers % n_stages`` one longer."""
    base, rem = divmod(n_layers, n_stages)
    out, start = [], 0
    for g in range(n_stages):
        size = base + (1 if g < rem else 0)
        out.append((start, start + size))
        start += size
    return out


class StageStacks:
    """A stacked leaf handed over stage by stage.

    The server slices each layer leaf of the full model by stage,
    ``leaf[lo:hi]``; this answers each such slice with the stage's own
    array, made at that size, so the chip never holds the whole stack
    and the stage copies side by side.
    """

    def __init__(self, by_range: dict):
        self._by_range = by_range

    def __getitem__(self, idx):
        key = (idx.start, idx.stop) if isinstance(idx, slice) else None
        if key not in self._by_range:
            raise IndexError(f"only whole stages can be taken: {idx!r}")
        return self._by_range.pop(key)


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _make_all(w: Widths, key, ranges: tuple, tied: bool, dtype):
    stages = []
    for lo, hi in ranges:
        layers = [layer_weights(w, key, l, dtype) for l in range(lo, hi)]
        stages.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers))
    return tuple(stages), top_weights(w, key, tied, dtype)


def served_params(w: Widths, seed: int, n_stages: int, tied: bool, dtype=jnp.bfloat16):
    """The full model's weight tree for the server, made on the device in
    one jitted call. The class leaves are :class:`StageStacks`."""
    ranges = tuple(stage_ranges(w.n_layers, n_stages))
    stages, top = _make_all(w, seed_key(seed), ranges, tied, dtype)
    c0: dict = {"attn": {}, "mlp": {}}
    for group, leaf in LAYER_LEAVES:
        stacks = StageStacks({
            r: (s[group][leaf] if group else s[leaf]) for r, s in zip(ranges, stages)
        })
        if group:
            c0[group][leaf] = stacks
        else:
            c0[leaf] = stacks
    embed = {"tok": top["tok"]}
    if not tied:
        embed["lm_head"] = top["lm_head"]
    return {"classes": {"c0": c0}, "embed": embed, "final_norm": top["final_norm"]}
