"""The dense decoder block: pre-norm, full causal attention on every
layer, dense SwiGLU.

A configuration file names its architecture module
(``"architecture": "dense"``), and the harness reaches everything it
assumes about the block through it (``bench.spec.load_architecture``):

- :func:`widths`: the sizes, from the file;
- :func:`program_model` and :func:`check_program`: the serving program's
  model built to the file, and the assertions that tie the file to the
  program's own configuration;
- :func:`served_params`: the weight tree ``PipelineServer`` takes;
- :func:`reference_logits`: the float32 reference, and its float8 control;
- :func:`token_work`: the work that ``step.mfu`` and
  ``paged_attention_roofline`` divide by.

Per layer: RMSNorm, attention with ``wq [D, H, Dh]``, ``wk``/``wv
[D, KV, Dh]``, ``wo [H, Dh, D]``, rotary positions (split halves, the
configuration's theta, every head dimension rotated) and grouped
key/value heads, residual; RMSNorm, SwiGLU ``wi_gate``/``wi_up [D, F]``,
``wo [F, D]``, residual.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.reference import _mm, _rmsnorm, _rope, causal_attention, teacher_forced
from bench.weights import StageStacks, _draw, seed_key, stage_ranges, top_weights
from bench.work import Work, _attn_call

__all__ = ["Widths", "widths", "program_model", "check_program", "served_params",
           "reference_logits", "token_work", "request_work"]


@dataclasses.dataclass(frozen=True)
class Widths:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 2  # bytes per cached key/value element (bf16)
    act_bytes: int = 2  # bytes per query/output element (bf16)

    @property
    def matmul_flops_per_position(self) -> int:
        """Projections and SwiGLU of one position through every layer."""
        D, H, KV, Dh, F = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.d_ff
        per_layer = 2 * (D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F)
        return self.n_layers * per_layer


def widths(c: dict) -> Widths:
    """The file's sizes; the head width is ``head_dim`` where the file gives
    it, and ``hidden_size // num_attention_heads`` where it does not."""
    return Widths(
        n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
    )


# ---------------------------------------------------------------------------
# The serving program's model
# ---------------------------------------------------------------------------


def program_model(config: dict):
    """The serving program's model, built to the configuration file."""
    from repro.configs import get_config
    from repro.models import build_model

    p = config["program"]
    head = {"d_head": config["head_dim"]} if config.get("head_dim") else {}
    cfg = dataclasses.replace(
        get_config(p["arch"]),
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"],
        param_dtype=config["torch_dtype"],
        attn_impl=p["attn_impl"],
        **head,
    )
    return build_model(cfg)


def check_program(config: dict) -> None:
    """Raise ``ValueError`` unless the program's own configuration of
    ``config["program"]["arch"]`` is this file's model: the same widths,
    table, rotary theta and norm eps, and a dense SwiGLU block with full
    attention on every layer."""
    from repro.configs import get_config

    p = get_config(config["program"]["arch"])
    pairs = {
        "n_layers": (p.n_layers, config["num_hidden_layers"]),
        "d_model": (p.d_model, config["hidden_size"]),
        "n_heads": (p.n_heads, config["num_attention_heads"]),
        "n_kv_heads": (p.n_kv_heads, config["num_key_value_heads"]),
        "head_dim": (p.head_dim, widths(config).head_dim),
        "d_ff": (p.d_ff, config["intermediate_size"]),
        "vocab_size": (p.vocab_size, config["vocab_size"]),
        "tie_embeddings": (p.tie_embeddings, config["tie_word_embeddings"]),
        "rope_theta": (p.rope_theta, config["rope_theta"]),
        "rms_eps": (p.rms_eps, config["rms_norm_eps"]),
        "act": (p.act, "swiglu"),
        "block": (p.block, "attn"),
        "attn_window": (p.attn_window, None),
    }
    off = {k: v for k, v in pairs.items() if v[0] != v[1]}
    if off:
        raise ValueError(f"{config['name']}: the program's {p.name} departs from the file "
                         f"(program, file): {off}")


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

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


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _make_all(w: Widths, key, ranges: tuple, tied: bool, dtype):
    stages = []
    for lo, hi in ranges:
        layers = [layer_weights(w, key, l, dtype) for l in range(lo, hi)]
        stages.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers))
    return tuple(stages), top_weights(w, key, tied, dtype)


def served_params(w: Widths, seed: int, n_stages: int, config: dict):
    """The full model's weight tree for the server, made on the device in
    one jitted call in the file's type. The class leaves are
    :class:`~bench.weights.StageStacks`."""
    tied = bool(config["tie_word_embeddings"])
    ranges = tuple(stage_ranges(w.n_layers, n_stages))
    stages, top = _make_all(w, seed_key(seed), ranges, tied, jnp.dtype(config["torch_dtype"]))
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


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, p, w: Widths, theta: float, eps: float, control: bool):
    """One decoder layer over a whole padded sequence x [S, D]."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    H, KV = w.n_heads, w.n_kv_heads
    h = _rmsnorm(x, p["ln1"], eps)
    q = _mm("sd,dhk->shk", h, p["attn"]["wq"], control, -1, 0)
    k = _mm("sd,dhk->shk", h, p["attn"]["wk"], control, -1, 0)
    v = _mm("sd,dhk->shk", h, p["attn"]["wv"], control, -1, 0)
    q, k = _rope(q, theta), _rope(k, theta)
    group = H // KV
    k = jnp.repeat(k, group, axis=1)  # head h reads key/value head h // group
    v = jnp.repeat(v, group, axis=1)
    o = causal_attention(q, k, v, control)
    x = x + _mm("shk,hkd->sd", o, p["attn"]["wo"], control, (-2, -1), (0, 1))
    h = _rmsnorm(x, p["ln2"], eps)
    gate = _mm("sd,df->sf", h, p["mlp"]["wi_gate"], control, -1, 0)
    up = _mm("sd,df->sf", h, p["mlp"]["wi_up"], control, -1, 0)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["mlp"]["wo"], control, -1, 0)


_layer_weights = jax.jit(layer_weights, static_argnums=(0,))


def reference_logits(w: Widths, seed: int, seqs, rows, config: dict, control: bool = False):
    """Float32 logits of each sequence at the positions ``rows[i]``
    (:func:`bench.reference.teacher_forced`), or with ``control`` the float8
    control's; the weights made again from the seed, layer by layer."""
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    key = seed_key(seed)

    def layer(l, xs):
        p = _layer_weights(w, key, l)
        return [_layer(x, p, w, theta, eps, control) for x in xs]

    return teacher_forced(w, key, seqs, rows, layer, eps=eps,
                          tied=bool(config["tie_word_embeddings"]), control=control)


# ---------------------------------------------------------------------------
# Work
# ---------------------------------------------------------------------------


def token_work(w: Widths, prompt_len: int, k: int, chunk: int) -> Work:
    """The work that delivers output token ``k`` (0-based) of a request:
    the whole chunked prefill for ``k == 0``, one decode position after
    (:mod:`bench.work`)."""
    if k == 0:
        af = ab = 0.0
        for start in range(0, prompt_len, chunk):
            f, b = _attn_call(w, start, min(chunk, prompt_len - start))
            af, ab = af + f, ab + b
        positions = prompt_len
    else:
        af, ab = _attn_call(w, prompt_len + k - 1, 1)
        positions = 1
    model = positions * w.matmul_flops_per_position + 2 * w.d_model * w.vocab + af
    return Work(model, af, ab)


def request_work(w: Widths, prompt_len: int, n_out: int, chunk: int) -> Work:
    total = Work()
    for k in range(n_out):
        total = total + token_work(w, prompt_len, k, chunk)
    return total
