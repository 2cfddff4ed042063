"""Multi-process serving tests.

Fast layer: the wire protocol and the worker-side ``StageHost`` run
in-process (no subprocess, no compile beyond the tiny smoke model) and
must match the monolithic greedy reference exactly.

Slow layer (tier-1 / the CI mesh lane): real worker processes — full
differential token exactness against the in-process engine, SIGKILL
failover with zero token loss, and respawn recovery.
"""

import io

import numpy as np
import pytest

from conftest import direct_greedy, tiny_model
from repro.serving import PipelineServer
from repro.serving.mpserve import (
    MPPipelineServer,
    StageHost,
    WorkerDied,
    _read_msg,
    _write_msg,
    build_from_spec,
)

SPEC = {
    "arch": "stablelm-1.6b",
    "smoke": True,
    "overrides": {"dtype": "float32", "param_dtype": "float32"},
    "seed": 0,
}


def test_refuses_an_accelerator_backend(monkeypatch):
    """One process per chip: on a TPU every worker would open a chip the
    coordinator already holds, so construction refuses up front."""
    import repro.serving.mpserve as mp

    monkeypatch.setattr(mp.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU harness"):
        MPPipelineServer(SPEC, n_groups=1, n_replicas=1)


class TestProtocol:
    def test_roundtrip(self):
        buf = io.BytesIO()
        msg = ("prefill", [0, 2], np.arange(6, dtype=np.int32).reshape(2, 1, 3))
        _write_msg(buf, msg)
        buf.seek(0)
        out = _read_msg(buf)
        assert out[0] == "prefill" and out[1] == [0, 2]
        np.testing.assert_array_equal(out[2], msg[2])

    def test_eof_raises_worker_died(self):
        with pytest.raises(WorkerDied):
            _read_msg(io.BytesIO(b"\x01\x02"))

    def test_truncated_frame_raises(self):
        buf = io.BytesIO()
        _write_msg(buf, {"ok": True})
        frame = buf.getvalue()[:-2]
        with pytest.raises(WorkerDied):
            _read_msg(io.BytesIO(frame))


class TestBuildFromSpec:
    def test_deterministic(self):
        import jax

        _, _, p1 = build_from_spec(SPEC)
        _, _, p2 = build_from_spec(SPEC)
        l1 = jax.tree_util.tree_leaves(p1)
        l2 = jax.tree_util.tree_leaves(p2)
        for a, b in zip(l1, l2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_overrides_applied(self):
        cfg, _, _ = build_from_spec(SPEC)
        assert cfg.dtype == "float32" and cfg.param_dtype == "float32"


class TestStageHostInProcess:
    """The worker's execution state, driven without a subprocess."""

    def test_single_stage_matches_direct_greedy(self):
        cfg, model, params = build_from_spec(SPEC)
        host = StageHost(SPEC, 0, 1, max_batch=4, max_len=64)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size, size=6)
        ref = direct_greedy(model, params, prompt, 5)
        r = host.handle(("prefill", [1], np.asarray(prompt, np.int32)[None, None, :]))
        toks = [int(r["tokens"][0])]
        for _ in range(4):
            r = host.handle(
                ("decode", [1], np.asarray([[[toks[-1]]]], np.int32))
            )
            toks.append(int(r["tokens"][0]))
        assert toks == ref

    def test_two_stage_handoff(self):
        """Stage-0 hidden handoff feeds stage 1; tokens match direct."""
        cfg, model, params = build_from_spec(SPEC)
        h0 = StageHost(SPEC, 0, 2, max_batch=4, max_len=64)
        h1 = StageHost(SPEC, 1, 2, max_batch=4, max_len=64)
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, cfg.vocab_size, size=5)
        ref = direct_greedy(model, params, prompt, 4)
        r0 = h0.handle(("prefill", [0], np.asarray(prompt, np.int32)[None, None, :]))
        r1 = h1.handle(("prefill", [0], r0["hidden"]))
        toks = [int(r1["tokens"][0])]
        for _ in range(3):
            r0 = h0.handle(("decode", [0], np.asarray([[[toks[-1]]]], np.int32)))
            r1 = h1.handle(("decode", [0], r0["hidden"]))
            toks.append(int(r1["tokens"][0]))
        assert toks == ref

    def test_unknown_op_errors(self):
        host = StageHost(SPEC, 0, 1, max_batch=2, max_len=32)
        with pytest.raises(ValueError, match="unknown op"):
            host.handle(("frobnicate",))


def _drain(server, reqs, limit=5000):
    for _ in range(limit):
        if all(r.done or r.dropped for r in reqs):
            return [list(r.generated) for r in reqs]
        server.step()
    raise RuntimeError("did not drain")


def _reference(prompts, n_tokens):
    _, model, params = build_from_spec(SPEC)
    ref = PipelineServer(
        model, params, n_groups=2, n_replicas=2,
        policy="uniform", max_len=64, max_batch=4, seed=3,
    )
    return _drain(ref, [ref.submit(p, n_tokens=n_tokens) for p in prompts])


@pytest.mark.slow
class TestMPServer:
    def test_differential_kill_and_recover(self):
        """One subprocess fleet end-to-end: exactness, SIGKILL failover
        (zero token loss, membership observed), respawn recovery."""
        cfg, _, _ = build_from_spec(SPEC)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (5, 9, 7, 5)]
        ref_a = _reference(prompts, 6)
        ref_b = _reference(prompts[:2], 4)
        with MPPipelineServer(
            SPEC, n_groups=2, n_replicas=2,
            policy="uniform", max_len=64, max_batch=4, seed=3,
        ) as mp:
            # wave 1: plain differential
            assert _drain(mp, [mp.submit(p, n_tokens=6) for p in prompts]) == ref_a

            # wave 2: kill the real process behind stage-0 replica 0
            # mid-stream. Stage 0's re-prefill rebuilds the full prompt +
            # generated prefix, so failover is loss-free and the stream
            # stays bit-exact. (A mid-pipeline kill re-prefills from the
            # latest hidden handoff — documented context loss — so it is
            # exercised for liveness elsewhere, not for exactness.)
            reqs = [mp.submit(p, n_tokens=4) for p in prompts[:2]]
            v0 = mp.router.membership_version
            for _ in range(3):
                mp.step()
            proc = mp._workers[(0, 0)].proc
            proc.kill()
            proc.wait()
            assert _drain(mp, reqs) == ref_b  # loss-free re-prefill
            assert mp.router.membership_version > v0
            assert not mp.budgets[0][0].alive
            # the dead member's routing rate is zeroed, sibling keeps mass
            rates = mp.router.long_term_rates
            assert rates is not None
            assert rates[0][0] == 0.0 and rates[0][1] > 0.0

            # recovery: respawn the worker, serve a third wave exactly
            mp.recover_replica(0, 0)
            assert mp._workers[(0, 0)].alive
            assert mp.budgets[0][0].alive
            assert _drain(mp, [mp.submit(p, n_tokens=4) for p in prompts[:2]]) == ref_b

    def test_unsupported_modes_raise(self):
        with pytest.raises(ValueError, match="dense whole-prompt"):
            MPPipelineServer(SPEC, paged=True)
        with pytest.raises(ValueError, match="dense whole-prompt"):
            MPPipelineServer(SPEC, prefill_chunk=4)
