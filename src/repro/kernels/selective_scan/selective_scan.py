"""Pallas TPU chunked selective-scan kernel (Mamba-1 recurrence).

TPU-native adaptation of the CUDA selective-scan (DESIGN.md Sec. 7): the
sequence is processed in chunks along the innermost (sequential) grid
dimension, and the state is carried across chunks in VMEM scratch (no
HBM round-trip per step). The state is kept transposed, ``[N, block_d]``:
the channel block fills the 128 lanes and the small state dimension the
sublanes, so a step's ``dt_t``/``x_t`` rows broadcast over sublanes and
the outer product ``B_t (dt_t x_t)`` and the read-out ``C_t h_t`` are
matmuls. A chunk runs as a loop over 8-step tiles (8 rows = one sublane
tile, loaded at aligned offsets), each unrolled.

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t
    y_t = <h_t, C_t>
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE = 8  # time steps per unrolled tile (one sublane tile)


def _scan_kernel(
    x_ref,  # [1, chunk, block_d]
    dt_ref,  # [1, chunk, block_d]
    b_ref,  # [1, chunk, N]
    c_ref,  # [1, chunk, N]
    at_ref,  # [N, block_d] — A transposed
    h0_ref,  # [1, N, block_d]
    y_ref,  # [1, chunk, block_d]
    hout_ref,  # [1, N, block_d]
    h_scr,  # VMEM [N, block_d] f32
):
    ci = pl.program_id(2)
    n_chunks = pl.num_programs(2)
    chunk = x_ref.shape[1]

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    at = at_ref[...].astype(jnp.float32)  # [N, block_d]

    def tile(j, h):
        rows = pl.ds(pl.multiple_of(j * _TILE, _TILE), _TILE)
        x = x_ref[0, rows, :].astype(jnp.float32)  # [8, block_d]
        dt = dt_ref[0, rows, :].astype(jnp.float32)
        bm = b_ref[0, rows, :].astype(jnp.float32)  # [8, N]
        cm = c_ref[0, rows, :].astype(jnp.float32)
        dx = dt * x
        ys = []
        for t in range(_TILE):
            inject = jax.lax.dot_general(
                bm[t : t + 1], dx[t : t + 1], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [N, block_d] outer product
            h = jnp.exp(dt[t : t + 1] * at) * h + inject
            ys.append(
                jax.lax.dot_general(
                    cm[t : t + 1], h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )  # [1, block_d]
        y_ref[0, rows, :] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // _TILE, tile, h_scr[...])

    @pl.when(ci == n_chunks - 1)
    def _final():
        hout_ref[0] = h_scr[...].astype(hout_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_d", "interpret")
)
def selective_scan_pallas(
    x: jax.Array,  # [B, S, Din]
    dt: jax.Array,  # [B, S, Din]
    Bmat: jax.Array,  # [B, S, N]
    Cmat: jax.Array,  # [B, S, N]
    A: jax.Array,  # [Din, N]
    h0: jax.Array | None = None,  # [B, Din, N]
    *,
    chunk: int = 128,
    block_d: int = 512,
    interpret: bool = False,
):
    """Returns (y [B, S, Din], h_final [B, Din, N])."""
    B, S, Din = x.shape
    N = A.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, Din, N), jnp.float32)

    chunk = -(-min(chunk, S) // _TILE) * _TILE
    block_d = min(block_d, Din)
    s_pad = -S % chunk
    d_pad = -Din % block_d
    if s_pad or d_pad:
        x = jnp.pad(x, ((0, 0), (0, s_pad), (0, d_pad)))
        dt = jnp.pad(dt, ((0, 0), (0, s_pad), (0, d_pad)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, s_pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, s_pad), (0, 0)))
    if d_pad:
        A = jnp.pad(A, ((0, d_pad), (0, 0)))
        h0 = jnp.pad(h0, ((0, 0), (0, d_pad), (0, 0)))
    Sp, Dp = S + s_pad, Din + d_pad
    n_chunks, n_d = Sp // chunk, Dp // block_d

    y, h_final = pl.pallas_call(
        _scan_kernel,
        grid=(B, n_d, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((N, block_d), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, Dp), x.dtype),
            jax.ShapeDtypeStruct((B, N, Dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        interpret=interpret,
    )(x, dt, Bmat, Cmat, A.T, h0.transpose(0, 2, 1))
    h_final = h_final.transpose(0, 2, 1)

    return y[:, :S, :Din], h_final[:, :Din]
