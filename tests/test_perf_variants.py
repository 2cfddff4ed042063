"""Perf-variant parity: every §Perf optimization must be numerically
equivalent to its baseline (debug-forward, not revert — see EXPERIMENTS.md)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeCell, get_smoke_config
from repro.models import build_model, init_from_template
from repro.models.inputs import make_inputs

CELL = ShapeCell("smoke", "train", seq_len=48, global_batch=2)


def build(name, **kw):
    cfg = dataclasses.replace(
        get_smoke_config(name), dtype="float32", param_dtype="float32", **kw
    )
    model = build_model(cfg)
    params = init_from_template(model.template, jax.random.PRNGKey(0), "float32")
    return cfg, model, params


def test_moe_gather_matches_einsum_dispatch():
    """Identical routing => identical outputs in the dropless regime, and
    equal outputs under drops too (same GShard position priority)."""
    for cap in (16.0, 1.0):  # dropless and capacity-dropping
        cfg_e, model_e, params = build("granite-moe-1b-a400m", capacity_factor=cap)
        cfg_g, model_g, _ = build(
            "granite-moe-1b-a400m", capacity_factor=cap, moe_impl="gather"
        )
        batch = make_inputs(cfg_e, CELL)
        le, _ = model_e.forward(params, batch)
        lg, _ = model_g.forward(params, batch)
        np.testing.assert_allclose(
            np.asarray(le), np.asarray(lg), rtol=2e-4, atol=2e-4,
            err_msg=f"capacity_factor={cap}",
        )


def test_decode_mulsum_matches_dot():
    cfg_d, model_d, params = build("qwen2.5-14b")
    cfg_m, model_m, _ = build("qwen2.5-14b", decode_mulsum=True)
    batch = make_inputs(cfg_d, CELL)
    tokens = batch["tokens"]
    S = tokens.shape[1]
    _, cache_d = model_d.prefill(params, dict(tokens=tokens[:, :-1]), S + 4)
    _, cache_m = model_m.prefill(params, dict(tokens=tokens[:, :-1]), S + 4)
    ld, _ = model_d.decode_step(params, tokens[:, -1:], cache_d)
    lm, _ = model_m.decode_step(params, tokens[:, -1:], cache_m)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lm), rtol=2e-4, atol=2e-4)


def test_kv_stream_matches_baseline():
    """attn_kv_stream (chunk-sliced K/V, bf16 dot operands) == baseline."""
    cfg_b, model_b, params = build("phi4-mini-3.8b")
    cfg_s, model_s, _ = build("phi4-mini-3.8b", attn_kv_stream=True)
    batch = make_inputs(cfg_b, CELL)
    lb, _ = model_b.forward(params, batch)
    ls, _ = model_s.forward(params, batch)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(ls), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("knob", ["decode_mulsum", "attn_kv_stream"])
def test_paged_decode_variant_matches_default(knob):
    """The paged XLA decode honours the dense-decode perf knobs: it
    traces a different program and gives the default path's logits."""
    cfg, model_d, params = build("qwen2.5-14b")
    _, model_v, _ = build("qwen2.5-14b", **{knob: True})
    W, NB, PAGE, L0 = 2, 4, 8, 5
    shape = (cfg.n_layers, W * NB + 1, PAGE, cfg.n_kv_heads, cfg.head_dim)
    pools = {"k": jnp.zeros(shape, jnp.float32), "v": jnp.zeros(shape, jnp.float32)}
    bt = jnp.arange(W * NB, dtype=jnp.int32).reshape(W, NB)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (W, L0)), jnp.int32)
    logits, pools = model_d.prefill_chunk_paged(
        params, prompt, pools, jnp.zeros((W,), jnp.int32),
        jnp.full((W,), L0, jnp.int32), bt,
    )
    args = (
        params, jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None],
        pools, jnp.full((W,), L0, jnp.int32), bt,
    )
    assert str(jax.make_jaxpr(model_d.decode_paged)(*args)) != str(
        jax.make_jaxpr(model_v.decode_paged)(*args)
    )
    ld, _ = model_d.decode_paged(*jax.tree_util.tree_map(jnp.array, args))
    lv, _ = model_v.decode_paged(*jax.tree_util.tree_map(jnp.array, args))
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lv), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ring_index_matches_roll():
    """Hymba ring-buffer decode far past the window, both ring impls."""
    cfg_r, model_r, params = build("hymba-1.5b")
    cfg_i, model_i, _ = build("hymba-1.5b", ring_impl="index")
    S = 3 * cfg_r.attn_window + 5
    cell = ShapeCell("long", "train", seq_len=S, global_batch=1)
    batch = make_inputs(cfg_r, cell, seed=5)
    tokens = batch["tokens"]
    n_prompt = S - 6
    _, cache_r = model_r.prefill(params, dict(tokens=tokens[:, :n_prompt]), S + 4)
    _, cache_i = model_i.prefill(params, dict(tokens=tokens[:, :n_prompt]), S + 4)
    for t in range(n_prompt, S):
        lr, cache_r = model_r.decode_step(params, tokens[:, t : t + 1], cache_r)
        li, cache_i = model_i.decode_step(params, tokens[:, t : t + 1], cache_i)
        np.testing.assert_allclose(
            np.asarray(lr), np.asarray(li), rtol=5e-4, atol=5e-4,
            err_msg=f"position {t}",
        )
