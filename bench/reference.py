"""The plain reference: a float32 forward pass of the served architecture.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: token table, then per
layer RMSNorm, attention with rotary positions (split halves, the
configuration's theta, every head dimension rotated) and grouped key/value
heads, residual, RMSNorm, SwiGLU, residual; a final RMSNorm and the head
(the token table when tied). It imports nothing of the serving program and
makes its weights again from the seed, layer by layer
(:mod:`bench.weights`), so it fits beside nothing the program holds.

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

from bench.weights import layer_weights, seed_key, top_weights
from bench.work import Widths

__all__ = ["reference_logits", "BUCKET"]

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


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, p, w: Widths, theta: float, eps: float, control: bool):
    """One decoder layer over a whole padded sequence x [S, D]."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    S = x.shape[0]
    H, KV, Dh = w.n_heads, w.n_kv_heads, w.head_dim
    h = _rmsnorm(x, p["ln1"], eps)
    q = _mm("sd,dhk->shk", h, p["attn"]["wq"], control, -1, 0)
    k = _mm("sd,dhk->shk", h, p["attn"]["wk"], control, -1, 0)
    v = _mm("sd,dhk->shk", h, p["attn"]["wv"], control, -1, 0)
    q, k = _rope(q, theta), _rope(k, theta)
    group = H // KV
    k = jnp.repeat(k, group, axis=1)  # head h reads key/value head h // group
    v = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(S)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=0)
        s = _mm("qhk,shk->hqs", qb, k, control, -1, -1) * Dh**-0.5
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return _mm("hqs,shk->qhk", pr, v, control, -1, 0)

    o = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK)).reshape(S, H, Dh)
    x = x + _mm("shk,hkd->sd", o, p["attn"]["wo"], control, (-2, -1), (0, 1))
    h = _rmsnorm(x, p["ln2"], eps)
    gate = _mm("sd,df->sf", h, p["mlp"]["wi_gate"], control, -1, 0)
    up = _mm("sd,df->sf", h, p["mlp"]["wi_up"], control, -1, 0)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["mlp"]["wo"], control, -1, 0)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head(x, final_norm, head, eps: float, tied: bool, control: bool):
    h = _rmsnorm(x, final_norm.astype(jnp.float32), eps)
    head = head.astype(jnp.float32)
    if tied:
        return _mm("sd,vd->sv", h, head, control, -1, -1)
    return _mm("sd,dv->sv", h, head, control, -1, 0)


_layer_weights = jax.jit(layer_weights, static_argnums=(0,))
_top_weights = jax.jit(top_weights, static_argnums=(0, 2))


def reference_logits(w: Widths, seed: int, seqs, rows, *, theta: float, eps: float,
                     tied: bool, control: bool = False):
    """Float32 logits of each sequence at the positions ``rows[i]``.

    ``seqs[i]``: int token ids of the whole sequence; ``rows[i]``: the
    positions whose next-token logits are wanted. Returns a list of
    ``[len(rows[i]), vocab]`` float32 NumPy arrays.
    """
    key = seed_key(seed)
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
        for layer in range(w.n_layers):
            p = _layer_weights(w, key, layer)
            xs = [_layer(x, p, w, theta, eps, control) for x in xs]
            del p
        head = tok if tied else top["lm_head"]
        out = []
        for x, r in zip(xs, rows):
            logits = _head(x[jnp.asarray(np.asarray(r, np.int32))], top["final_norm"],
                           head, eps, tied, control)
            out.append(np.asarray(logits, np.float32))
    return out
