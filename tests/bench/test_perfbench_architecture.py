"""Architecture modules: the dense module pinned to the numbers the
harness gave before it had modules, and a new architecture added by
files alone.

The pinned numbers are literals: work counts at both configuration
files' widths, and SHA-256 digests of the weight tree the server takes
and of the reference's logits (control off and on) at the CPU cuts of
``test_perfbench_oracle.py``. A change that moves any of them changes
what ``step.mfu``, ``paged_attention_roofline`` or ``correct`` read.
"""

import hashlib
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import run  # noqa: E402
from bench.spec import load_architecture, load_cell  # noqa: E402
from bench.weights import stage_ranges  # noqa: E402
from test_perfbench_oracle import CUTS, SEED  # noqa: E402

STABLELM = "stablelm-1.6b.g3r2"
PHI4 = "phi4-mini-3.8b.g2r2"
DENSE = load_architecture({"architecture": "dense"})


def config(name, cut=False):
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    if cut:
        c.update(CUTS[name])
    return c


# (model FLOPs, attention FLOPs, attention bytes) of output token k of a
# request with the prompt length given, in 256-token chunks.
WORK = {
    (STABLELM, 300, 0): (749163118592, 8876851200, 168296448),
    (STABLELM, 1500, 12): (3174563840, 297271296, 297467904),
    (STABLELM, 3072, 127): (3506241536, 628948992, 629145600),
    (PHI4, 300, 0): (1951718178816, 17753702400, 190840832),
    (PHI4, 1500, 12): (8266186752, 594542592, 198574080),
    (PHI4, 3072, 127): (8929542144, 1257897984, 419692544),
}


@pytest.mark.parametrize("name, prompt_len, k", sorted(WORK))
def test_dense_token_work_is_pinned(name, prompt_len, k):
    c = config(name)
    arch = load_architecture(c)
    assert arch is DENSE
    t = arch.token_work(arch.widths(c), prompt_len, k, 256)
    assert (t.model_flops, t.attn_flops, t.attn_bytes) == WORK[(name, prompt_len, k)]


PARAMS = {
    STABLELM: "499a562cfe19819a3d8a38bbc72c2328baa44aad5315c7b2303a6710925c7ac6",
    PHI4: "a92455f0a38fe0bdea167979e520302284970f495e80e0536682cba061bc0020",
}


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_dense_served_params_are_pinned(name):
    """Every leaf of the tree ``PipelineServer`` takes, stage by stage, in
    the file's type."""
    c = config(name, cut=True)
    w, n_stages = DENSE.widths(c), c["deployment"]["n_groups"]
    tree = DENSE.served_params(w, SEED, n_stages, c)
    h = hashlib.sha256()
    c0 = tree["classes"]["c0"]
    for group, leaf in DENSE.LAYER_LEAVES:
        stacks = c0[group][leaf] if group else c0[leaf]
        for lo, hi in stage_ranges(w.n_layers, n_stages):
            h.update(np.asarray(stacks[lo:hi]).tobytes())
    for k in sorted(tree["embed"]):
        h.update(np.asarray(tree["embed"][k]).tobytes())
    h.update(np.asarray(tree["final_norm"]).tobytes())
    assert h.hexdigest() == PARAMS[name]


REFERENCE = {
    (STABLELM, False): "3a717d596427e2037425ec99b43dadb8b10f3873ae9644c404ec103bac8f80b9",
    (STABLELM, True): "e2c1eca59247ef7caaa63f44f7aa9ea1aba1a2dbafd34066c07fbc7d8aed1d0a",
    (PHI4, False): "db635a640a24421261444ad5419cbb1b736158290456829a56143f12e9ea8aec",
    (PHI4, True): "72948ff5606506441209a2048506c0550080b879dd272a929c25e2f981884cdf",
}


@pytest.mark.parametrize("name, control", sorted(REFERENCE), ids=lambda v: str(v))
def test_dense_reference_logits_are_pinned(name, control):
    c = config(name, cut=True)
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 256, n).astype(np.int32) for n in (40, 100)]
    rows = [np.arange(n - 20, n) for n in (40, 100)]
    logits = DENSE.reference_logits(DENSE.widths(c), SEED, seqs, rows, c, control=control)
    h = hashlib.sha256()
    for a in logits:
        h.update(np.ascontiguousarray(a, np.float32).tobytes())
    assert h.hexdigest() == REFERENCE[(name, control)]


def test_head_width_comes_from_the_file():
    """A file whose ``head_dim`` is not ``hidden_size // num_attention_heads``
    (Mellum2's 128 against 2304 / 32 = 72): widths, weights and work use
    the file's."""
    c = dict(config(STABLELM), num_hidden_layers=1, hidden_size=2304, num_attention_heads=32,
             num_key_value_heads=4, head_dim=128, intermediate_size=896, vocab_size=98304)
    w = DENSE.widths(c)
    assert w.head_dim == 128
    assert DENSE.widths(dict(c, head_dim=None)).head_dim == 72
    t = DENSE.token_work(w, 1000, 1, 256)
    # Decode position 1000 reads 1001 positions of 4 key/value heads of 128.
    assert t.attn_flops == 4 * 32 * 128 * 1001
    assert t.attn_bytes == 2 * 1001 * 4 * 128 * 2 + 2 * 32 * 128 * 2
    assert w.matmul_flops_per_position == 2 * (2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
                                               + 3 * 2304 * 896)
    assert DENSE._leaf_shape_std(w, "attn", "wo")[0] == (32, 128, 2304)


@pytest.mark.parametrize("change", [
    {"num_hidden_layers": 25}, {"intermediate_size": 5120}, {"head_dim": 128},
    {"rope_theta": 1e6}, {"rms_norm_eps": 1e-5}, {"tie_word_embeddings": True},
], ids=lambda d: next(iter(d)))
def test_check_program_refuses_a_file_that_departs(change):
    c = dict(config(STABLELM), **change)
    with pytest.raises(ValueError, match="departs"):
        DENSE.check_program(c)


# A second architecture, as a later change would add it: a module that
# reuses the dense one, with each layer's two residual branches halved.
# The program serves it as dense weights whose branch outputs (attn.wo,
# mlp.wo) are halved, exactly in bf16; its reference halves the same
# leaves of the layers it makes again from the seed.
TOY = '''"""The dense block with each residual branch halved."""
from pathlib import Path

from bench.reference import teacher_forced
from bench.spec import load_architecture
from bench.weights import StageStacks, seed_key

dense = load_architecture({"architecture": "dense"}, Path(__file__).resolve().parents[2])
widths, program_model, check_program, token_work = (
    dense.widths, dense.program_model, dense.check_program, dense.token_work)
SCALE = 0.5


def served_params(w, seed, n_stages, config):
    params = dense.served_params(w, seed, n_stages, config)
    c0 = params["classes"]["c0"]
    for group in ("attn", "mlp"):
        by_range = c0[group]["wo"]._by_range
        c0[group]["wo"] = StageStacks({r: a * SCALE for r, a in by_range.items()})
    return params


def reference_logits(w, seed, seqs, rows, config, control=False):
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    key = seed_key(seed)

    def layer(l, xs):
        p = dense._layer_weights(w, key, l)
        p = dict(p, **{g: dict(p[g], wo=p[g]["wo"] * SCALE) for g in ("attn", "mlp")})
        return [dense._layer(x, p, w, theta, eps, control) for x in xs]

    return teacher_forced(w, key, seqs, rows, layer, eps=eps,
                          tied=bool(config["tie_word_embeddings"]), control=control)
'''

TINY_MIX = {
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 4, "max": 60},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4, "max": 20},
    "arrivals": "poisson",
    "energy": {"harvest": [60, 80]},
    "events": [],
}


def copy_with_a_cell(tmp_path, architecture, arch_source=None):
    """A copy of the benchmark tree with a CPU-sized configuration that
    names ``architecture``, a traffic mix and a cell added: new files,
    and new entries in ``BENCHMARK.json``."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if arch_source is not None:
        (root / "bench" / "architectures" / f"{architecture}.py").write_text(arch_source)
    cfg = config(STABLELM, cut=True)
    cfg.update(name="toy-tiny", architecture=architecture)
    cfg["deployment"].update(max_batch=4, max_len=96, max_pages=40, prefill_chunk=16)
    cfg["correct"]["sample_tokens"] = 40
    (root / "bench" / "configs" / "toy-tiny.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    (root / "bench" / "cells" / "toy-tiny.tiny.json").write_text(
        json.dumps({"rate_per_s": 4.0, "drain_s": 60}))
    spec["configs"].append({"name": "toy-tiny", "source": "a test", "file": "bench/configs/toy-tiny.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "toy-tiny.tiny", "config": "toy-tiny", "traffic": "tiny",
                              "chips": 1, "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_new_architecture_is_added_by_files_alone(tmp_path):
    root = copy_with_a_cell(tmp_path, "toy", TOY)
    # No file the benchmark had is edited; BENCHMARK.json only gains entries.
    for path in (ROOT / "bench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            assert (root / path.relative_to(ROOT)).read_bytes() == path.read_bytes(), path
    spec, old = (json.loads((r / "BENCHMARK.json").read_text()) for r in (root, ROOT))
    assert spec.keys() == old.keys()
    assert all(spec[k] == old[k] for k in old if k not in ("configs", "workloads"))
    assert all(spec[k][:len(old[k])] == old[k] for k in ("configs", "workloads"))

    cell = load_cell("toy-tiny.tiny", root=root)
    assert Path(cell.arch.__file__) == (root / "bench" / "architectures" / "toy.py").resolve()
    res = run.run_cell(cell, SEED, 2.0, False, require_chip=False)
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["correct"], res["checks"]

    # The toy's reference is not the dense one: the comparison that passed
    # was made against the toy's own.
    w = cell.arch.widths(cell.config)
    seqs, rows = [np.arange(40, dtype=np.int32)], [np.arange(20, 40)]
    toy = cell.arch.reference_logits(w, SEED, seqs, rows, cell.config)[0]
    dense = DENSE.reference_logits(w, SEED, seqs, rows, cell.config)[0]
    assert np.abs(toy - dense).max() > 1.0
    assert (toy.argmax(axis=1) != dense.argmax(axis=1)).any()


@pytest.mark.parametrize("architecture", ["nonesuch", None])
def test_configuration_naming_no_module_fails_at_load(tmp_path, architecture):
    root = copy_with_a_cell(tmp_path, architecture)
    if architecture is None:
        path = root / "bench" / "configs" / "toy-tiny.json"
        cfg = json.loads(path.read_text())
        del cfg["architecture"]
        path.write_text(json.dumps(cfg))
        with pytest.raises(KeyError, match="names no"):
            load_cell("toy-tiny.tiny", root=root)
        return
    with pytest.raises(FileNotFoundError) as e:
        load_cell("toy-tiny.tiny", root=root)
    assert str((root / "bench" / "architectures" / "nonesuch.py").resolve()) in str(e.value)


def test_dense_run_at_the_files_head_width_is_correct():
    """A whole CPU run of a dense file whose ``head_dim`` (32) is not
    ``hidden_size // num_attention_heads`` (16): the program is built with
    the file's head width, and its tokens agree with the reference's."""
    from test_perfbench_oracle import tiny_cell

    cell = tiny_cell()
    cell.config.update(num_key_value_heads=2, head_dim=32)
    assert DENSE.widths(cell.config).head_dim == 32
    assert DENSE.program_model(cell.config).cfg.head_dim == 32
    res = run.run_cell(cell, SEED, 2.0, False, require_chip=False)
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["correct"], res["checks"]
