"""Property-based cross-engine differential test (hypothesis).

Randomized traces — cache layout x KV dtype x async depth x power mode
x arrival pattern — driven through the synchronous engine
(``async_depth=0``) and the async engine must produce identical token
streams, and ``async_depth=1`` must degenerate to the sync engine
*exactly* (same ServerStats, not just same tokens).

Skipped cleanly when hypothesis is not installed (the container image
does not bake it in); the deterministic trace matrix in
``test_async_engine.py`` covers the named configurations either way.
"""

import dataclasses

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from conftest import tiny_model  # noqa: E402

from repro.core.power import fixed_policy  # noqa: E402
from repro.serving import PipelineServer  # noqa: E402

MODEL = None


def _model():
    global MODEL
    if MODEL is None:
        MODEL = tiny_model()
    return MODEL


# One trace shape: every degree of freedom the async refactor touches.
TRACES = st.fixed_dictionaries(
    {
        "paged": st.booleans(),
        "int8": st.booleans(),  # applied only when paged
        "prefill_chunk": st.sampled_from([None, 4]),
        "kappa_pm": st.integers(min_value=1, max_value=3),
        "staggered": st.booleans(),
        "n_requests": st.integers(min_value=2, max_value=5),
        "n_tokens": st.integers(min_value=1, max_value=4),
        "seed": st.integers(min_value=0, max_value=3),
    }
)


def _run(depth: int, t: dict):
    cfg, model, params = _model()
    server = PipelineServer(
        model,
        params,
        n_groups=2,
        n_replicas=2,
        policy="uniform",
        pm_policy=fixed_policy(t["kappa_pm"]),
        harvest_bounds=(60.0, 80.0),
        max_len=64,
        max_batch=4,
        paged=t["paged"],
        page_size=8,
        kv_dtype="int8" if (t["paged"] and t["int8"]) else None,
        prefill_chunk=t["prefill_chunk"],
        async_depth=depth,
        seed=t["seed"],
    )
    reqs = []
    steps = 0
    n_sub = 0
    while n_sub < t["n_requests"] or not all(
        r.done or r.dropped for r in reqs
    ):
        while n_sub < t["n_requests"]:
            req = server.submit(
                (np.arange(4 + n_sub) + n_sub) % cfg.vocab_size,
                t["n_tokens"],
            )
            if req is not None:
                reqs.append(req)
            n_sub += 1
            if t["staggered"]:
                break
        server.step()
        steps += 1
        assert steps < 5000, "trace did not drain"
    return [tuple(r.generated) for r in reqs], server.stats


@pytest.mark.slow
class TestAsyncProperty:
    @settings(
        max_examples=20,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(trace=TRACES, depth=st.integers(min_value=1, max_value=3))
    def test_async_tokens_equal_sync(self, trace, depth):
        sync_tokens, _ = _run(0, trace)
        async_tokens, _ = _run(depth, trace)
        assert async_tokens == sync_tokens

    @settings(
        max_examples=10,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(trace=TRACES)
    def test_depth1_is_sync_exactly(self, trace):
        sync_tokens, sync_stats = _run(0, trace)
        d1_tokens, d1_stats = _run(1, trace)
        assert d1_tokens == sync_tokens
        assert dataclasses.asdict(d1_stats) == dataclasses.asdict(sync_stats)
