"""The work functions against counts made by hand at the benchmark's widths."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.spec import load_architecture  # noqa: E402

DENSE = load_architecture({"architecture": "dense"})
token_work, request_work = DENSE.token_work, DENSE.request_work


def widths(name):
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return load_architecture(c).widths(c)


STABLELM = "stablelm-1.6b.g3r2"
PHI4 = "phi4-mini-3.8b.g2r2"


def test_widths_from_the_configuration_files():
    w = widths(STABLELM)
    assert (w.n_layers, w.d_model, w.n_heads, w.n_kv_heads, w.head_dim, w.d_ff, w.vocab) == (
        24, 2048, 32, 32, 64, 5632, 100352)
    w = widths(PHI4)
    assert (w.n_layers, w.d_model, w.n_heads, w.n_kv_heads, w.head_dim, w.d_ff, w.vocab) == (
        32, 3072, 24, 8, 128, 8192, 200064)


def test_matmul_flops_per_position():
    # 2 x (q 2048x2048 + k, v 2 x 2048x2048 + o 2048x2048 + 3 x 2048x5632) x 24
    assert widths(STABLELM).matmul_flops_per_position == 24 * 102760448
    # 2 x (q 3072x3072 + k, v 2 x 3072x1024 + o 3072x3072 + 3 x 3072x8192) x 32
    assert widths(PHI4).matmul_flops_per_position == 32 * 201326592


def test_first_token_carries_the_chunked_prefill():
    w = widths(STABLELM)
    t = token_work(w, 300, 0, 256)
    # Chunks [0, 256) and [256, 300): attention over 1..300 positions.
    attn = 24 * 4 * 32 * 64 * (300 * 301 // 2)
    assert t.attn_flops == attn == 8876851200
    # Per layer: chunk 1 reads 256 K/V rows and 256 q/o rows, chunk 2 300 and 44.
    per_layer = (2 * 256 * 4096 + 2 * 256 * 4096) + (2 * 300 * 4096 + 2 * 44 * 4096)
    assert t.attn_bytes == 24 * per_layer == 168296448
    assert t.model_flops == 300 * 24 * 102760448 + 2 * 2048 * 100352 + attn


def test_decode_token_reads_its_context_once():
    w = widths(STABLELM)
    t = token_work(w, 300, 1, 256)
    assert t.attn_flops == 24 * 4 * 2048 * 301
    assert t.attn_bytes == 24 * (2 * 301 * 4096 + 2 * 4096)
    assert t.model_flops == 24 * 102760448 + 411041792 + 24 * 4 * 2048 * 301


def test_gqa_decode_at_phi4_widths():
    w = widths(PHI4)
    t = token_work(w, 4000, 1, 256)
    assert t.attn_flops == 32 * 4 * 24 * 128 * 4001 == 1573257216
    # 8 key/value heads of 128 in bf16: 4096 bytes per position for k and v.
    assert t.attn_bytes == 32 * (2 * 4001 * 8 * 128 * 2 + 2 * 24 * 128 * 2) == 524812288


@pytest.mark.parametrize("name", [STABLELM, PHI4])
def test_request_is_the_sum_of_its_tokens(name):
    w = widths(name)
    whole = request_work(w, 700, 5, 256)
    parts = [token_work(w, 700, k, 256) for k in range(5)]
    assert whole.model_flops == pytest.approx(sum(p.model_flops for p in parts))
    assert whole.attn_bytes == pytest.approx(sum(p.attn_bytes for p in parts))
    # Attention FLOPs of a request: every position over its causal prefix.
    n = 700 + 5 - 1
    assert whole.attn_flops == pytest.approx(w.n_layers * 4 * w.n_heads * w.head_dim * n * (n + 1) / 2)
