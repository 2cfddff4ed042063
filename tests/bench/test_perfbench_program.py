"""The readers of the program's own spans and counters.

The four per-layer metrics that read what ``PipelineServer`` counts
(``ServerStats``), on hand-built window differences, and at a parent
whose server lacks the counters; the reduction of program spans
(``bench/program_trace.py``) on hand-built spans and on the recorded v5e
trace, which holds none; and a whole traced run on the CPU, in which
every one of the four reads a number.
"""

import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench.program_trace import innermost, label_gaps, launch_to_module, span_table  # noqa: E402
from bench.spec import load_metric  # noqa: E402
from bench.trace_reduce import Trace, read_trace  # noqa: E402

TRACE = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"
NEW = ("engine.readback_wait_ms", "engine.launch_ms", "sched.first_token_steps",
       "sched.token_gap_steps")


def _read(name, **stats):
    return load_metric(name).read(types.SimpleNamespace(stats=stats))


@pytest.mark.parametrize("name, stats, value", [
    ("engine.readback_wait_ms", dict(traced_readback_s=3.0, traced_steps=100), 30.0),
    ("engine.launch_ms", dict(traced_launch_s=0.5, traced_calls=250), 2.0),
    ("sched.first_token_steps", dict(first_token_steps=190, first_tokens=10), 19.0),
    ("sched.token_gap_steps", dict(token_gap_steps=546, token_gaps=182), 3.0),
])
def test_reader_divides_the_window_differences(name, stats, value):
    assert _read(name, **stats) == pytest.approx(value)
    # Nothing counted in the denominator: no reading.
    zero = {k: (0 if i else v) for i, (k, v) in enumerate(stats.items())}
    assert _read(name, **zero) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_where_the_server_lacks_the_counter(name):
    """A server from before these counters: the reader returns nothing
    and does not raise."""
    assert _read(name, slots=100, tokens_generated=40) is None


def test_recorded_trace_keeps_its_gap_labels():
    """The v5e trace holds no program span: each gap keeps the label
    ``Trace.idle_gaps`` gives it."""
    tr = read_trace(str(TRACE))
    assert label_gaps(tr, []) == tr.idle_gaps()


def test_gap_is_named_by_the_innermost_program_span():
    tr = read_trace(str(TRACE))
    (label, length), *_ = tr.idle_gaps(1)
    lo, hi = tr.window
    # The longest gap lies in a bench.client span; put a step and its
    # commit over the whole trace, and a readback inside the commit.
    program = [("serve.step", lo, hi, {}), ("serve.commit", lo + 1e-6, hi - 1e-6, {}),
               ("serve.readback", lo + 2e-6, lo + 3e-6, {"call": 0})]
    assert label_gaps(tr, program, 1) == [[f"{label} > serve.commit", length]]


def test_innermost_and_span_table():
    program = [("serve.step", 0.0, 1.0, {}), ("serve.dispatch", 0.1, 0.5, {}),
               ("serve.call", 0.2, 0.4, {}), ("serve.launch", 0.3, 0.4, {}),
               ("serve.commit", 0.5, 0.9, {}), ("serve.readback", 0.6, 0.8, {}),
               ("serve.step", 1.0, 2.0, {}), ("serve.readback", 1.5, 1.7, {})]
    assert innermost(program, 0.35) == "serve.launch"
    assert innermost(program, 0.45) == "serve.dispatch"
    assert innermost(program, 0.95) == "serve.step"
    assert innermost(program, 2.5) is None
    table = span_table(program, (0.0, 1.2))
    assert (table["steps"], table["calls"]) == (2, 1)
    assert table["spans"]["serve.readback"]["count"] == 1  # the second starts after 1.2
    assert table["spans"]["serve.readback"]["per_step_ms"] == pytest.approx(100.0)
    assert table["spans"]["serve.launch"]["per_call_ms"] == pytest.approx(100.0)


def test_launch_is_matched_to_the_stage_program_it_issued():
    dev = "/device:TPU:0"
    tr = Trace(device=[(dev, "XLA Modules", "jit_decode_fn(1)", 0.098, 0.120),
                       (dev, "XLA Modules", "jit_argmax(2)", 0.121, 0.122),
                       (dev, "XLA Modules", "jit_chunk_pages(3)", 0.205, 0.300)],
               host=[], window=(0.0, 0.4))
    program = [("serve.launch", 0.1, 0.101, {}), ("serve.launch", 0.2, 0.201, {})]
    assert launch_to_module(tr, program) == [pytest.approx(-0.002), pytest.approx(0.005)]


def test_traced_cpu_run_reads_all_four():
    """A whole ``--trace 1`` run of a CPU-sized cell: each of the four
    reads a number, and the step counts come out as whole pipelines."""
    from test_perfbench_oracle import SEED, tiny_cell

    cell = tiny_cell()
    cell.per_layer = [{"name": n, "unit": load_metric(n).UNIT} for n in NEW]
    res = run.run_cell(cell, SEED, 2.0, True, require_chip=False)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == set(NEW), res["metrics"]
    assert m["engine.readback_wait_ms"] > 0 and m["engine.launch_ms"] > 0
    # Three stages, at least one step each.
    assert m["sched.token_gap_steps"] >= 3 and m["sched.first_token_steps"] >= 3
