"""The trace reduction on a small trace recorded on one TPU v5e.

``data/small_trace.xplane.pb``: three calls of a jitted ``decode_fn``
(the paged attention kernel over 4 x 1000 positions of 32 heads of 64,
then a [4, 2048] x [2048, 4096] product), each started in an
``engine.dispatch`` span, waited for in an ``engine.commit`` span and
followed by 20 ms of sleep in a ``bench.client`` span.
"""

import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.peaks import PEAKS  # noqa: E402
from bench.spec import load_metric  # noqa: E402
from bench.stats import union_length  # noqa: E402
from bench.trace_reduce import op_name, read_trace  # noqa: E402
from bench.work import Work  # noqa: E402

TRACE = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return read_trace(str(TRACE))


def test_window_and_busy_time(tr):
    assert tr.devices() == ["/device:TPU:0"]
    # From 10 ms (SKEW_S) before the first host span, 45.469149 ms into
    # the trace, to the end of the last, 113.911657 ms.
    assert tr.window == (pytest.approx(0.035469149, abs=1e-9), pytest.approx(0.113911657, abs=1e-9))
    assert tr.window_s == pytest.approx(0.078442508, abs=1e-9)
    assert tr.busy_s() == pytest.approx(0.003103227, abs=1e-9)
    # Busy time is the union of the op intervals, and the ops fill the
    # three program executions.
    modules = tr.module_events(r"^jit_decode_fn\b")
    assert tr.busy_s() == pytest.approx(union_length([(s, e) for _, s, e in modules]), rel=1e-3)


def test_program_and_kernel_times(tr):
    modules = tr.module_events(r"^jit_decode_fn\b")
    assert [round((e - s) * 1e9) for _, s, e in modules] == [1034382, 1034542, 1034373]
    kernel = tr.op_events(r"^paged_attention$")
    assert [round((e - s) * 1e9) for _, s, e in kernel] == [714535, 714535, 714537]
    for (_, ks, ke), (_, ms, me) in zip(kernel, modules):
        assert ms <= ks and ke <= me
    assert tr.top_ops(1) == [["paged_attention", pytest.approx(0.002143607, abs=1e-9)]]


def test_idle_gaps_are_named_by_the_host_span(tr):
    gaps = tr.idle_gaps(4)
    assert [g[0] for g in gaps] == ["host: bench.client"] * 3 + ["host: outside any span"]
    assert all(0.020 < g[1] < 0.025 for g in gaps[:3])
    # The first device op starts 7.97 ms into the window (2.04 ms before
    # the first host span: the device clock's skew).
    assert gaps[3][1] == pytest.approx(0.007965048, abs=1e-9)


def test_op_names():
    assert op_name("%paged_attention.1 = f32[4,32] custom-call(s32[4] %x)") == "paged_attention"
    assert op_name("%copy-start.2 = (s32[4,64]) copy-start(s32[4,64] %bt.1)") == "copy-start"
    assert op_name("%fusion.12.3 = bf16[2] fusion()") == "fusion"


def test_readers_on_the_recorded_trace(tr):
    ctx = types.SimpleNamespace(trace=tr, peaks=PEAKS["TPU v5 lite"],
                                work=Work(model_flops=3 * 2 * 4 * 2048 * 4096,
                                          attn_flops=3 * 4 * 4 * 32 * 64 * 1000,
                                          attn_bytes=3 * 2 * 4 * 1000 * 32 * 64 * 2))
    assert load_metric("model.decode_ms").read(ctx) == pytest.approx(1.034432, abs=1e-6)
    assert load_metric("model.prefill_ms").read(ctx) is None  # no chunk program traced
    idle = load_metric("device.idle_share").read(ctx)
    assert idle == pytest.approx(100 * (1 - 0.003103227 / 0.078442508), abs=1e-6)
    # 3 x 65.5 MB of K/V at 819 GB/s over 3 x 0.7145 ms of kernel time.
    roof = load_metric("paged_attention_roofline").read(ctx)
    assert roof == pytest.approx(100 * (3 * 2 * 4 * 1000 * 32 * 64 * 2 / 819e9) / 0.002143607, rel=1e-6)
    assert 0 < roof < 100
    # The model FLOPs given here, the three [4, 2048] x [2048, 4096]
    # products, over the three jit_decode_fn executions at 197 TFLOP/s.
    mfu = load_metric("step.mfu").read(ctx)
    assert mfu == pytest.approx(100 * 3 * 2 * 4 * 2048 * 4096 / (0.003103297 * 197e12), rel=1e-6)
