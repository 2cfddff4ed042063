"""PipelineServer traces itself: spans on the profiler's clock and step
counts for first tokens and token gaps.

A bare server, with no observer installed, runs under
``jax.profiler.trace`` on the CPU; the written ``.xplane.pb`` is read
back with ``bench.program_trace.read_program`` and the span tree checked
against the one ``repro.serving.readback`` documents.
"""

import dataclasses
import glob
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.power import fixed_policy
from repro.models import build_model, init_from_template
from repro.serving import PipelineServer, readback

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.program_trace import read_program  # noqa: E402

CHUNK = 4
MODEL = None


def _model():
    """A three-layer smoke stablelm: one layer per stage at G=3."""
    global MODEL
    if MODEL is None:
        cfg = dataclasses.replace(
            get_smoke_config("stablelm-1.6b"), dtype="float32", param_dtype="float32", n_layers=3
        )
        model = build_model(cfg)
        MODEL = cfg, model, init_from_template(model.template, jax.random.PRNGKey(0), "float32")
    return MODEL


def _server(paged=True, async_depth=2, max_batch=4):
    """G=3 stages, gate open: PM3 (kappa=1) on every call."""
    cfg, model, params = _model()
    return cfg, PipelineServer(
        model, params, n_groups=3, n_replicas=1, policy="uniform",
        pm_policy=fixed_policy(3), harvest_bounds=(60.0, 80.0), max_len=64,
        max_batch=max_batch, paged=paged, page_size=8, prefill_chunk=CHUNK,
        async_depth=async_depth, seed=0,
    )


def _serve(server, cfg, prompt_lens, n_tokens=3):
    reqs = [server.submit((np.arange(n) + i) % cfg.vocab_size, n_tokens=n_tokens)
            for i, n in enumerate(prompt_lens)]
    for _ in range(200):
        if all(r.done for r in reqs):
            return reqs
        server.step()
    raise AssertionError("requests did not finish")


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _within(spans, name, outer):
    return [s for s in spans if s[0] == name and _inside(s, outer)]


@pytest.fixture(scope="module", params=[True, False], ids=["paged", "dense"])
def traced(request, tmp_path_factory):
    """Five requests through a server with two lanes a stage, so that some
    wait in the queue and are admitted inside a step."""
    assert readback.observer() is None
    cfg, server = _server(paged=request.param, max_batch=2)
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        reqs = _serve(server, cfg, [8, 9, 5, 12, 6])
    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return server, reqs, read_program(path)


def test_the_span_tree_nests(traced):
    server, _, spans = traced
    steps = [s for s in spans if s[0] == "serve.step"]
    assert [s[3]["step_num"] for s in steps] == list(range(1, server.stats.slots + 1))
    for step in steps:
        phases = [s for s in spans if s[0] in ("serve.sched", "serve.dispatch", "serve.commit")
                  and _inside(s, step)]
        assert [p[0] for p in phases] == ["serve.sched", "serve.dispatch", "serve.commit"]
    parents = {
        "serve.sched": "serve.step", "serve.dispatch": "serve.step", "serve.commit": "serve.step",
        "serve.call": "serve.dispatch", "serve.inputs": "serve.call",
        "serve.launch": "serve.call", "serve.readback": "serve.commit",
    }
    for s in spans:
        if s[0] in parents:
            assert any(_inside(s, p) for p in spans if p[0] == parents[s[0]]), s
    # Admissions from the queue happen inside a step, in its scheduling part.
    admits = [s for s in spans if s[0] == "sched.admit"
              and any(_inside(s, st) for st in steps)]
    assert admits
    assert all(any(_inside(a, p) for p in spans if p[0] == "serve.sched") for a in admits)


def test_each_call_is_tiled_by_its_inputs_and_launch(traced):
    server, _, spans = traced
    calls = [s for s in spans if s[0] == "serve.call"]
    assert len(calls) == server.stats.stage_calls
    for call in calls:
        inputs = _within(spans, "serve.inputs", call)
        launch = _within(spans, "serve.launch", call)
        assert len(inputs) == len(launch) >= 1
        assert all(i[2] <= la[1] for i, la in zip(inputs, launch))


def test_calls_name_their_members_and_readbacks_their_call(traced):
    server, reqs, spans = traced
    calls = {s[3]["call"]: s for s in spans if s[0] == "serve.call"}
    assert sorted(calls) == list(range(server.stats.stage_calls))
    seen = set()
    for call in calls.values():
        a = call[3]
        rids = [int(x) for x in str(a["rids"]).split()]
        assert len(rids) == a["chunk_lanes"] + a["decode_lanes"]
        assert 0 <= a["chunk_tokens"] <= CHUNK * a["chunk_lanes"]
        assert (a["pm"], a["kappa"]) == (3, 1) and 0 <= a["g"] < 3 and a["r"] == 0
        seen.update(rids)
    assert seen == {r.rid for r in reqs}
    readbacks = [s for s in spans if s[0] == "serve.readback"]
    assert readbacks
    for rb in readbacks:
        call = calls[rb[3]["call"]]
        assert call[3]["g"] == 2 and call[2] <= rb[1]


def test_recorded_spans_add_up_in_the_stats(traced):
    """The ``traced_*`` counters are what the profiler recorded: the
    steps and calls, and the host time of the ``serve.readback`` and
    ``serve.launch`` spans."""
    server, _, spans = traced
    st = server.stats
    assert st.traced_steps == st.slots == sum(s[0] == "serve.step" for s in spans)
    assert st.traced_calls == st.stage_calls
    for name, total in (("serve.readback", st.traced_readback_s),
                        ("serve.launch", st.traced_launch_s)):
        mine = [s[2] - s[1] for s in spans if s[0] == name]
        assert total > 0
        assert sum(mine) == pytest.approx(total, rel=0.05, abs=1e-4 * len(mine))


def test_no_span_argument_is_built_while_no_profiler_records(monkeypatch):
    """Every span's arguments come from a callable that runs only while
    a profiler records: count the calls, with tracing off and on."""
    built, made = [], []
    init = readback.span.__init__

    def counting_init(self, name, args=None):
        if args is not None:
            made.append(name)
            inner = args

            def args():
                built.append(name)
                return inner()

        init(self, name, args)

    monkeypatch.setattr(readback.span, "__init__", counting_init)
    cfg, server = _server(max_batch=2)
    _serve(server, cfg, [8, 9, 5])
    assert {"serve.step", "serve.call", "serve.readback", "sched.admit"} <= set(made)
    assert built == []
    monkeypatch.setattr(readback, "tracing", lambda: True)
    _serve(server, cfg, [7])
    assert {"serve.step", "serve.call", "serve.readback"} <= set(built)


@pytest.mark.parametrize("async_depth", [0, 2])
def test_step_counts_of_a_scripted_request(async_depth):
    """One request of a two-chunk prompt, G=3, kappa=1: each stage takes
    two steps of prefill, so the first token lands in step 6; each later
    token takes one decode step per stage, 3."""
    cfg, server = _server(async_depth=async_depth)
    (req,) = _serve(server, cfg, [2 * CHUNK], n_tokens=3)
    st = server.stats
    assert (st.first_token_steps, st.first_tokens) == (6, 1)
    assert (st.token_gap_steps, st.token_gaps) == (6, 2)
    assert (st.slots, st.stage_calls) == (12, 12)
    assert req.ttft_slots == 6 and req.slot_last_token == 12
    # No profiler recorded: the traced counters stay at zero.
    assert (st.traced_steps, st.traced_calls, st.traced_readback_s, st.traced_launch_s) == (
        0, 0, 0.0, 0.0)


def test_ttft_is_stamped_after_the_readback(monkeypatch):
    """``t_first_token`` is the moment the token lands in ``generated``:
    after the readback that brought it to the host, and within the step
    in which a client polling between steps first sees it."""
    read_done = []
    read = PipelineServer._read

    def slow_read(self, dev):
        time.sleep(0.05)
        out = read(self, dev)
        read_done.append(time.perf_counter())
        return out

    monkeypatch.setattr(PipelineServer, "_read", slow_read)
    cfg, server = _server()
    req = server.submit(np.arange(CHUNK) % cfg.vocab_size, n_tokens=2)
    while not req.generated:
        server.step()
    seen = time.perf_counter()
    assert read_done and read_done[0] <= req.t_first_token <= seen
