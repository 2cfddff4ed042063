"""GPipe-style pipeline parallelism with shard_map + ppermute.

The paper's Petals groups are pipeline stages over WAN replicas; on a TPU
mesh the same structure maps to a ``stage`` mesh axis: each device along
the axis holds one stage's weights, microbatches stream through
``lax.ppermute`` in a single fused SPMD program (n_micro + n_stages - 1
ticks), and the bubble shrinks as n_micro grows.

``stage_fn(params, x) -> y`` must be shape-preserving on the hidden
microbatch (embedding/unembedding live inside the first/last stage's
params — :mod:`repro.serving.partition` produces exactly that layout).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe", "pipeline_apply"]


def gpipe(
    stage_fn: Callable,
    *,
    n_stages: int,
    n_micro: int,
    axis: str = "stage",
) -> Callable:
    """Per-device GPipe schedule (call inside shard_map over ``axis``)."""

    def run(params_local, micro_inputs):
        s = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        hidden_shape = micro_inputs.shape[1:]
        buf0 = jnp.zeros(hidden_shape, micro_inputs.dtype)
        outs0 = jnp.zeros((n_micro,) + hidden_shape, micro_inputs.dtype)

        def tick(carry, t):
            buf, outs = carry
            mb = t - s
            active = (mb >= 0) & (mb < n_micro)
            mb_c = jnp.clip(mb, 0, n_micro - 1)
            inp0 = jax.lax.dynamic_index_in_dim(micro_inputs, mb_c, 0, keepdims=False)
            inp = jnp.where(s == 0, inp0, buf)
            out = stage_fn(params_local, inp)
            out = jnp.where(active, out, jnp.zeros_like(out))
            updated = jax.lax.dynamic_update_index_in_dim(outs, out, mb_c, 0)
            outs = jnp.where(active & (s == n_stages - 1), updated, outs)
            if n_stages > 1:
                nxt = jax.lax.ppermute(
                    out, axis, [(i, i + 1) for i in range(n_stages - 1)]
                )
            else:
                nxt = out
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
        # Only the last stage holds real outputs (zeros elsewhere): psum
        # broadcasts them to every stage device.
        return jax.lax.psum(outs, axis) if n_stages > 1 else outs

    return run


def pipeline_apply(
    mesh: Mesh,
    stage_fn: Callable,
    stacked_params,
    inputs: jax.Array,
    *,
    n_micro: int,
    axis: str = "stage",
):
    """Run the pipeline over ``inputs`` [batch, ...].

    ``stacked_params``: leaves with leading dim n_stages (stage-sharded on
    ``axis``). Returns outputs with the input batch layout.
    """
    n_stages = mesh.shape[axis]
    B = inputs.shape[0]
    if B % n_micro:
        raise ValueError("batch must divide into microbatches")
    micro = inputs.reshape(n_micro, B // n_micro, *inputs.shape[1:])

    run = gpipe(stage_fn, n_stages=n_stages, n_micro=n_micro, axis=axis)

    def body(params_local, micro_all):
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        return run(params_local, micro_all)

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, micro)
    return out.reshape(B, *out.shape[2:])
