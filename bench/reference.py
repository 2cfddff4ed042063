"""The plain reference: what every architecture module's float32 forward
pass shares.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: the token table, the
architecture's layers, a final RMSNorm and the head (the token table when
tied). It imports nothing of the serving program and makes its weights
again from the seed, layer by layer, so it fits beside nothing the
program holds. The layers are the architecture module's
(``bench/architectures/<name>.py``); the pieces they are built from are
here: :func:`_rmsnorm`, rotary positions (:func:`_rope`), blocked causal
attention (:func:`causal_attention`) and matrix products that the control
rounds (:func:`_mm`).

A sequence is teacher-forced whole: prompt and served tokens together,
padded at the end to a multiple of ``BUCKET`` so few shapes compile
(causal attention leaves the real positions untouched by the padding).
Attention runs in blocks of ``Q_BLOCK`` query rows.

``control=True`` computes the same in 8-bit floating point (e4m3): every
matrix product takes operands rounded to float8 with one scale per row of
the activations and per output channel of the weights, the step below
the bfloat16 the configurations state. It is the control that the
comparison has to fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import top_weights

__all__ = ["BUCKET", "causal_attention", "teacher_forced"]

BUCKET = 512
Q_BLOCK = 512
HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fq(x, axis):
    """Round ``x`` to float8 e4m3 with one scale along ``axis`` (the
    contracted one), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(spec, x, w, control, x_axis, w_axis):
    if control:
        x, w = _fq(x, x_axis), _fq(w, w_axis)
    return jnp.einsum(spec, x, w, precision=HI)


def _rmsnorm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, theta):
    """x [S, heads, Dh]; position of row s is s."""
    S, _, Dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # [S, Dh/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, control: bool):
    """Full causal attention of a whole padded sequence, in blocks of
    ``Q_BLOCK`` query rows: q, k, v [S, H, Dh] (key/value heads already
    repeated to the query heads), scores scaled by ``Dh ** -0.5``;
    returns [S, H, Dh]."""
    S, H, Dh = q.shape
    kpos = jnp.arange(S)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=0)
        s = _mm("qhk,shk->hqs", qb, k, control, -1, -1) * Dh**-0.5
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return _mm("hqs,shk->qhk", pr, v, control, -1, 0)

    return jax.lax.map(block, jnp.arange(0, S, Q_BLOCK)).reshape(S, H, Dh)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head(x, final_norm, head, eps: float, tied: bool, control: bool):
    h = _rmsnorm(x, final_norm.astype(jnp.float32), eps)
    head = head.astype(jnp.float32)
    if tied:
        return _mm("sd,vd->sv", h, head, control, -1, -1)
    return _mm("sd,dv->sv", h, head, control, -1, 0)


_top_weights = jax.jit(top_weights, static_argnums=(0, 2))


def teacher_forced(w, key, seqs, rows, layer, *, eps: float, tied: bool, control: bool):
    """Float32 logits of each sequence at the positions ``rows[i]``.

    ``w`` has ``n_layers``, ``d_model`` and ``vocab``; ``key`` is the
    seed's (:func:`bench.weights.seed_key`). ``layer(l, xs)`` runs layer
    ``l`` over every padded sequence ``xs`` ([S, D] float32 each) and
    returns them. ``seqs[i]``: int token ids of the whole sequence;
    ``rows[i]``: the positions whose next-token logits are wanted.
    Returns a list of ``[len(rows[i]), vocab]`` float32 NumPy arrays.
    """
    top = _top_weights(w, key, tied)
    tok = top["tok"]
    xs = []
    for s in seqs:
        S = len(s)
        pad = -(-S // BUCKET) * BUCKET
        ids = np.zeros((pad,), np.int32)
        ids[:S] = s
        xs.append(tok[jnp.asarray(ids)].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        for l in range(w.n_layers):
            xs = layer(l, xs)
        head = tok if tied else top["lm_head"]
        out = []
        for x, r in zip(xs, rows):
            logits = _head(x[jnp.asarray(np.asarray(r, np.int32))], top["final_norm"],
                           head, eps, tied, control)
            out.append(np.asarray(logits, np.float32))
    return out
