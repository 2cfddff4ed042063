"""Weights from a seed: the pieces every architecture module shares.

Each leaf of each layer is drawn from its own key, ``fold_in`` of the
seed's key with the layer and the leaf, so one layer can be made again
alone: a reference does that, layer by layer, and never reads what the
program holds. The program gets the weights of a pipeline stage as one
stacked array per leaf, made on the device in one jitted call in the type
they are served in, and handed over stage by stage (:class:`StageStacks`).
The layer's leaves are the architecture module's
(``bench/architectures/<name>.py``); the token table ``[V, D]``, the
final RMSNorm and, when not tied, ``lm_head [D, V]`` are here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["seed_key", "top_weights", "stage_ranges", "StageStacks"]


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed, wider than 32 bits too."""
    seed = int(seed) & (2**64 - 1)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _draw(key, shape, std, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    # Norm gains sit near 1 but not at it, so the check sees them applied.
    x = 1.0 + 0.1 * x if std is None else x * std
    return x.astype(dtype)


def top_weights(w, key, tied: bool, dtype=jnp.bfloat16) -> dict:
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
