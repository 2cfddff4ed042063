"""BENCHMARK.json and the files it names agree; the harness refuses a
device it cannot measure."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.peaks import NoChip, peaks_for  # noqa: E402
from bench.spec import load_cell, load_metric  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(cell):
    c = load_cell(cell)
    assert c.load["rate_per_s"] > 0 and c.load["drain_s"] > 0
    assert c.config["name"] == next(w["config"] for w in SPEC["workloads"] if w["name"] == cell)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_metric_has_a_reader_that_says_what_it_is(metric):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    mod = load_metric(metric)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (entry["unit"], entry["layer"], entry["moves"])
    assert callable(mod.read)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_configuration_is_the_programs_model_at_published_widths(entry):
    from repro.configs import get_config

    c = json.loads((ROOT / entry["file"]).read_text())
    p = get_config(c["program"]["arch"])
    assert (p.n_layers, p.d_model, p.n_heads, p.n_kv_heads, p.d_ff, p.vocab_size) == (
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["intermediate_size"], c["vocab_size"])
    assert p.tie_embeddings == c["tie_word_embeddings"]
    assert p.rope_theta == c["rope_theta"] and p.rms_eps == c["rms_norm_eps"]
    assert p.act == "swiglu" and p.block == "attn" and p.attn_window is None


def test_peaks_refuse_other_devices():
    assert peaks_for("tpu", "TPU v5 lite").flops_bf16 == 197e12
    with pytest.raises(NoChip):
        peaks_for("cpu", "cpu")
    with pytest.raises(NoChip):
        peaks_for("tpu", "TPU v9 imaginary")


def test_run_on_a_cpu_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
