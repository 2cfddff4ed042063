"""The comparison that decides ``correct``, on the CPU at a small size.

A whole run of the harness (set-up, open-loop window, drain, reference)
with the look for a chip skipped: sound, it comes out correct; with the
timed path broken underneath, or a request refused, it does not. The
float8 control, put in the program's place, comes out not correct by the
same verdict.
"""

import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench.spec import Cell  # noqa: E402
from bench.traffic import Mix  # noqa: E402

SEED = 2**33 + 12345


# Each configuration file cut to a CPU-sized model of the same kind:
# multi-head (stablelm) and grouped-query with a tied table (phi4-mini).
CUTS = {
    "stablelm-1.6b.g3r2": dict(num_hidden_layers=3, hidden_size=64, intermediate_size=128,
                               num_attention_heads=4, num_key_value_heads=4, vocab_size=256),
    "phi4-mini-3.8b.g2r2": dict(num_hidden_layers=2, hidden_size=96, intermediate_size=192,
                                num_attention_heads=6, num_key_value_heads=2, vocab_size=256),
}


def tiny_cell(config="stablelm-1.6b.g3r2"):
    """A configuration file of the benchmark, cut to a CPU-sized model."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    cfg.update(CUTS[config])
    cfg["deployment"].update(max_batch=4, max_len=96, max_pages=40, prefill_chunk=16)
    cfg["correct"]["sample_tokens"] = 40
    mix = Mix.from_dict("tiny", {
        "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 4, "max": 60},
        "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4, "max": 20},
        "energy": {"harvest": [60, 80]},
    })
    e2e = [{"name": n, "unit": u} for n, u in
           [("ttft_p50_s", "s"), ("itl_p50_ms", "ms"), ("tokens_per_s", "tokens/s"), ("setup_s", "s")]]
    return Cell("tiny", 1, cfg, mix, {"rate_per_s": 4.0, "drain_s": 60}, e2e, [])


def run_tiny(config="stablelm-1.6b.g3r2"):
    return run.run_cell(tiny_cell(config), SEED, 2.0, False, require_chip=False)


@pytest.mark.parametrize("config", sorted(CUTS))
def test_sound_run_is_correct(config):
    res = run_tiny(config)
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ttft_p50_s", "itl_p50_ms", "tokens_per_s", "setup_s"}


def _alter_token(monkeypatch):
    from repro.serving.engine import PipelineServer

    emit = PipelineServer._emit_token

    def altered(self, req, token, *a, **k):
        return emit(self, req, (token + 1) % self.cfg.vocab_size, *a, **k)

    monkeypatch.setattr(PipelineServer, "_emit_token", altered)


def _drop_stage_handoff(monkeypatch):
    from repro.serving.engine import PipelineServer

    commit = PipelineServer._commit

    def dropped(self, req, out, g, *a, **k):
        kind, value, adv = out
        if g < self.G - 1 and value is not None:
            value = jnp.zeros_like(value)
        return commit(self, req, (kind, value, adv), g, *a, **k)

    monkeypatch.setattr(PipelineServer, "_commit", dropped)


@pytest.mark.parametrize("fault", [_alter_token, _drop_stage_handoff],
                         ids=["token_altered", "stage_handoff_dropped"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run_tiny()
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > res["checks"]["max_logit_gap"]["limit"]


def test_refused_request_is_not_correct(monkeypatch):
    """One request of the window refused at ``submit``: the served tokens
    of the others still match the reference, but the run is not correct."""
    from repro.serving.engine import PipelineServer

    submit, refused = PipelineServer.submit, []

    def refuse_one(self, tokens, n_tokens=8):
        if n_tokens > run.WARMUP_TOKENS and not refused:
            refused.append(n_tokens)
            return None
        return submit(self, tokens, n_tokens)

    monkeypatch.setattr(PipelineServer, "submit", refuse_one)
    res = run_tiny()
    assert refused and res["failed"] == 1
    assert res["checks"]["max_logit_gap"]["value"] <= res["checks"]["max_logit_gap"]["limit"]
    assert res["correct"] is False
    assert res["checks"]["failed"] == {"value": 1, "limit": 0}


def test_warm_up_reaches_every_lane_count(monkeypatch):
    """The warm-up's bursts, through ``submit`` and ``step`` alone, give a
    later stage calls of every lane count, chunked prefill and decode, so
    no hand-off of the window compiles there."""
    from repro.serving import engine

    seen = set()
    for name in ("run_chunks", "run_decode"):
        def counted(self, r, jobs, *a, _run=getattr(engine._PagedExec, name), _name=name, **k):
            if self.g > 0:
                seen.add((_name, len(jobs)))
            return _run(self, r, jobs, *a, **k)

        monkeypatch.setattr(engine._PagedExec, name, counted)
    cell = tiny_cell()
    server = run.build_server(cell, SEED)
    run.warm_lanes(server, server.max_batch, 24, 256, SEED)
    lanes = range(1, server.max_batch + 1)
    assert seen >= {(name, n) for name in ("run_chunks", "run_decode") for n in lanes}


def test_float8_control_in_the_programs_place_is_not_correct():
    """A whole run, with the float8 control's tokens in the place of the
    served ones: the run's own verdict says not correct, and the
    program's, on the same sample, correct."""
    res = run.run_cell(tiny_cell(), SEED, 2.0, False, require_chip=False, control=True)
    assert res["program"]["correct"] is True
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > res["checks"]["max_logit_gap"]["limit"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_float8_control_reads_above_the_limit(seed):
    """The reference in float8 in the program's place, at a quarter of the
    stablelm configuration's width and 4 layers: the token it puts first
    lies further below the float32 reference's best than the limit, and
    the run's verdict says not correct."""
    cfg = json.loads((ROOT / "bench" / "configs" / "stablelm-1.6b.g3r2.json").read_text())
    cfg.update(num_hidden_layers=4, hidden_size=512, intermediate_size=1408,
               num_attention_heads=8, num_key_value_heads=8, vocab_size=2048)
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, 2048, 40).astype(np.int32), [0] * 120) for _ in range(4)]
    correct, checks = run.verdict(cfg, 0, run.logit_gaps(cfg, SEED + seed, sample, control=True))
    assert correct is False
    assert checks["max_logit_gap"]["value"] > cfg["correct"]["max_logit_gap"]
