"""Speculative draft-verify decoding: differential + lemma tests.

The headline claim of the draft-verify refactor: greedy accept makes
speculation a pure latency optimization, so the same seeded trace
driven through the plain paged engine and the speculative engine (any
``spec_k``, any ``async_depth``, fp32 or int8 pools) must produce
bit-for-bit identical token streams — because

* one ``verify_step_paged`` chunk call reproduces, per position, the
  exact logits sequential ``decode_step_paged`` calls would have
  produced (the per-row reductions are independent of the other rows
  and the scattered page rows are byte-identical — the lemma tests
  below pin both);
* the accept finalizer commits only the longest verified prefix and
  rolls every stage's optimistic KV advance back to the committed
  stream (``KVCacheManager.rollback``), so a rejected draft leaves no
  phantom context;
* an aborted round (failover, drop) is rewound by
  ``StepScheduler.rewind_spec`` to exactly the state a plain decode
  round would have left.

Known, documented exception: mid-pipeline (stage > 0) failover recovery
re-prefills from the latest hidden handoff, which is lossy in the
existing engine; multi-token rounds reach a given failure step at
different progress than single-token rounds, so plain-vs-spec equality
is asserted at G=1 (token-exact stage-0 recovery) while G>=2 failover
asserts depth-invariance, page conservation, and completion instead.

Also here: a seeded random-ops fuzzer for ``rollback(n)`` (page
conservation + block-table consistency after every op; the hypothesis
twin lives in ``test_property_spec.py``), the verify path's zero-new-
gathers guarantee at the jaxpr level, the spec engine's host-sync
contract (no dispatch-phase syncs at any depth), and ServerStats
acceptance accounting.
"""

import dataclasses

import numpy as np
import pytest
from conftest import direct_greedy, tiny_model

from repro.core.power import fixed_policy
from repro.serving import PipelineServer

MODEL = None


def _model():
    global MODEL
    if MODEL is None:
        MODEL = tiny_model()
    return MODEL


def _server(depth, spec_k=None, **kw):
    """Paged server; ``spec_k`` switches on self-draft speculation (the
    draft IS the target model, so fp32 acceptance is ~1.0 — correctness
    must hold for any draft, which the pairing test covers)."""
    cfg, model, params = _model()
    defaults = dict(
        n_groups=1, n_replicas=2, policy="uniform",
        harvest_bounds=(50.0, 60.0), max_len=64, max_batch=4,
        paged=True, page_size=8, seed=0,
    )
    defaults.update(kw)
    if spec_k is not None:
        defaults.update(spec_draft=(model, params), spec_k=spec_k)
    return cfg, PipelineServer(model, params, async_depth=depth, **defaults)


def _prompt(cfg, n, prompt_len=4):
    return (np.arange(prompt_len + n) + n) % cfg.vocab_size


def _run_trace(depth, *, spec_k=None, kappa_pm=None, staggered=False,
               fail_steps=(), recover_steps=(), n_requests=5, n_tokens=6,
               prompt_len=4, **kw):
    """One seeded trace (same shape as the async differential harness):
    submissions, optional failover/recovery, drained to completion."""
    if kappa_pm is not None:
        kw.setdefault("pm_policy", fixed_policy(kappa_pm))
        kw.setdefault("harvest_bounds", (60.0, 80.0))
    cfg, server = _server(depth, spec_k=spec_k, **kw)
    fail = dict(fail_steps)
    recover = dict(recover_steps)
    reqs = []
    steps = 0
    n_sub = 0
    while n_sub < n_requests or not all(r.done or r.dropped for r in reqs):
        while n_sub < n_requests:
            req = server.submit(_prompt(cfg, n_sub, prompt_len), n_tokens)
            if req is not None:
                reqs.append(req)
            n_sub += 1
            if staggered:
                break
        for g, r in fail.get(steps, ()):
            server.fail_replica(g, r)
        for g, r in recover.get(steps, ()):
            server.recover_replica(g, r)
        server.step()
        steps += 1
        assert steps < 5000, "trace did not drain"
    return [tuple(r.generated) for r in reqs], server, reqs


class TestSpecDifferential:
    """Spec streams must be bit-for-bit equal to plain paged decode."""

    @pytest.mark.parametrize("kv", [None, "int8"], ids=["fp32", "int8"])
    def test_spec_matches_plain(self, kv):
        """{k=2,4} x {depth 0,2} against one plain baseline per pool
        dtype: identical tokens, and speculation actually engaged."""
        kw = dict(kv_dtype=kv)
        base, _, _ = _run_trace(0, **kw)
        assert any(len(t) > 0 for t in base)
        for k in (2, 4):
            for depth in (0, 2):
                toks, server, _ = _run_trace(depth, spec_k=k, **kw)
                assert toks == base, f"spec k={k} depth={depth} diverged ({kv})"
                st = server.stats
                assert st.spec_rounds > 0
                assert st.accepted_tokens == st.tokens_generated

    def test_spec_matches_direct_greedy(self):
        """The end-to-end oracle: spec streams equal direct greedy
        decoding of the same prompts on the raw model."""
        toks, _, _ = _run_trace(2, spec_k=4, n_requests=3)
        cfg, model, params = _model()
        for n, t in enumerate(toks):
            assert list(t) == direct_greedy(model, params, _prompt(cfg, n), 6)

    def test_spec_pipeline_g2(self):
        """Two pipeline stages: stage 0 drafts + verifies tokens, stage 1
        verifies the hidden handoff in its own chunk call. Streams still
        match plain at both depths."""
        kw = dict(n_groups=2, n_replicas=1)
        base, _, _ = _run_trace(0, **kw)
        for depth in (0, 2):
            toks, server, _ = _run_trace(depth, spec_k=4, **kw)
            assert toks == base, f"G=2 spec depth={depth} diverged"
            assert server.stats.spec_rounds > 0

    @pytest.mark.parametrize("kv", [None, "int8"], ids=["fp32", "int8"])
    def test_failover_token_exact(self, kv):
        """G=1 mid-flight failover + recovery: rewind_spec discards the
        in-flight round, stage-0 re-prefill is loss-free, so spec still
        equals plain bit-for-bit."""
        trace = dict(
            kv_dtype=kv, kappa_pm=2, staggered=True,
            fail_steps={6: [(0, 0)]}, recover_steps={12: [(0, 0)]},
        )
        base, _, _ = _run_trace(0, **trace)
        assert any(len(t) > 0 for t in base)
        for depth in (0, 2):
            toks, server, _ = _run_trace(depth, spec_k=4, **trace)
            assert toks == base, f"spec depth={depth} diverged after failover"
            assert server.stats.rerouted_stages > 0

    def test_g2_failover_depth_invariant(self):
        """Mid-pipeline failover recovery re-prefills from the hidden
        handoff (lossy by design), so plain equality cannot hold at
        G>=2 — but the spec engine must still be exactly depth-invariant,
        conserve pages, and drain every request."""
        trace = dict(
            n_groups=2, n_replicas=2, kappa_pm=2, staggered=True,
            fail_steps={3: [(0, 0)], 6: [(1, 1)]},
            recover_steps={9: [(0, 0)], 11: [(1, 1)]},
        )
        t0, s0, r0 = _run_trace(0, spec_k=4, **trace)
        t2, s2, r2 = _run_trace(2, spec_k=4, **trace)
        assert t0 == t2, "spec G=2 failover streams depend on async depth"
        for server, reqs in ((s0, r0), (s2, r2)):
            assert server.stats.rerouted_stages > 0
            assert all(r.done or r.dropped for r in reqs)
            for mgr in server.managers.values():
                mgr.check_conservation()

    def test_preemption_token_exact(self):
        """Page pool too small for every context: preemption/requeue
        churn rewinds in-flight rounds (victims re-prefill from the
        committed stream), tokens stay identical to plain and to the
        direct greedy oracle."""
        trace = dict(
            max_pages=7, n_groups=1, n_replicas=1,
            n_requests=3, n_tokens=24, prompt_len=10,
        )
        base, s0, _ = _run_trace(0, **trace)
        assert s0.stats.preempted_jobs > 0
        cfg, model, params = _model()
        for n, t in enumerate(base):
            assert list(t) == direct_greedy(
                model, params, _prompt(cfg, n, 10), 24
            )
        for depth in (0, 2):
            toks, server, _ = _run_trace(depth, spec_k=4, **trace)
            assert toks == base, f"spec depth={depth} diverged under preemption"
            assert server.stats.preempted_jobs > 0

    @pytest.mark.slow
    def test_spec_k_sweep(self):
        """Any draft depth (including k=1 and k > remaining tokens)
        yields the same stream."""
        base, _, _ = _run_trace(0)
        for k in (1, 3, 6, 9):
            toks, server, _ = _run_trace(2, spec_k=k)
            assert toks == base, f"spec k={k} diverged"
            assert server.stats.spec_rounds > 0


class TestSpecStats:
    def test_acceptance_accounting(self):
        _, server, _ = _run_trace(2, spec_k=4, n_tokens=8)
        st = server.stats
        assert st.spec_rounds > 0
        assert st.draft_calls > 0
        assert st.verify_calls > 0
        assert st.spec_accepted <= st.spec_proposed
        assert 0.0 < st.acceptance_rate <= 1.0
        # Self-draft at fp32: the draft replays the target's greedy path.
        assert st.acceptance_rate > 0.9
        assert st.accepted_tokens == st.tokens_generated > 0
        assert st.energy_charged > 0.0
        # Speculation must beat one-dispatch-per-token on dispatch count.
        assert st.verify_calls + st.draft_calls < st.accepted_tokens

    def test_plain_engine_accounting_unchanged(self):
        _, server, _ = _run_trace(0)
        st = server.stats
        assert st.spec_rounds == st.draft_calls == st.verify_calls == 0
        assert st.spec_proposed == st.spec_accepted == 0
        assert st.acceptance_rate == 0.0
        assert st.accepted_tokens == st.tokens_generated > 0
        assert st.energy_charged > 0.0

    @pytest.mark.slow
    def test_pairing_draft_model(self):
        """A *different* draft model (registry-style pairing, here with
        random weights: acceptance ~0) must still produce the plain
        stream — verification, not the draft, owns correctness."""
        import jax

        from repro.models import build_model, init_from_template

        cfg, model, params = _model()
        draft = build_model(cfg)
        dparams = init_from_template(
            draft.template, jax.random.PRNGKey(7), "float32"
        )
        base, _, _ = _run_trace(0)
        server = PipelineServer(
            model, params, n_groups=1, n_replicas=2, policy="uniform",
            harvest_bounds=(50.0, 60.0), max_len=64, max_batch=4,
            paged=True, page_size=8, async_depth=2,
            spec_draft=(draft, dparams), spec_k=4, seed=0,
        )
        reqs = [server.submit(_prompt(cfg, n), 6) for n in range(5)]
        steps = 0
        while not all(r.done or r.dropped for r in reqs):
            server.step()
            steps += 1
            assert steps < 5000
        assert [tuple(r.generated) for r in reqs] == base
        # Every round still commits the verify's own bonus token.
        assert server.stats.spec_rounds > 0
        assert server.stats.accepted_tokens == server.stats.tokens_generated


class TestSpecValidation:
    def test_requires_paged_substrate(self):
        cfg, model, params = _model()
        with pytest.raises(ValueError, match="paged"):
            PipelineServer(
                model, params, n_groups=1, n_replicas=1,
                harvest_bounds=(50.0, 60.0), max_len=64, max_batch=2,
                spec_draft=(model, params),
            )

    def test_requires_positive_k(self):
        cfg, model, params = _model()
        with pytest.raises(ValueError, match="spec_k"):
            PipelineServer(
                model, params, n_groups=1, n_replicas=1,
                harvest_bounds=(50.0, 60.0), max_len=64, max_batch=2,
                paged=True, spec_draft=(model, params), spec_k=0,
            )


class TestVerifyLemma:
    """The kernel-level fact the engine's exactness rests on: one
    ``verify_step_paged`` chunk call == k+1 sequential
    ``decode_step_paged`` calls, bit-for-bit, in logits AND in the page
    rows it scatters."""

    W, PAGE, NB, L0, K = 2, 8, 4, 5, 5

    def _pools(self, cfg, kv_dtype):
        import jax.numpy as jnp

        P = self.W * self.NB  # + 1 scratch page at index P
        shape = (cfg.n_layers, P + 1, self.PAGE, cfg.n_kv_heads, cfg.head_dim)
        pools = {
            "k": jnp.zeros(shape, jnp.dtype(kv_dtype)),
            "v": jnp.zeros(shape, jnp.dtype(kv_dtype)),
        }
        if jnp.dtype(kv_dtype) == jnp.int8:
            pools["k_scale"] = jnp.ones(shape[:3], jnp.float32)
            pools["v_scale"] = jnp.ones(shape[:3], jnp.float32)
        return pools

    @pytest.mark.parametrize(
        "impl,kv", [("xla", None), ("pallas", None), ("pallas", "int8")],
        ids=["xla", "pallas", "pallas-int8"],
    )
    def test_verify_chunk_equals_sequential_decode(self, impl, kv):
        import jax
        import jax.numpy as jnp

        from repro.models import build_model

        cfg, _, params = _model()
        model = build_model(dataclasses.replace(cfg, attn_impl=impl))
        kv_dtype = kv or cfg.dtype
        W, L0, K = self.W, self.L0, self.K
        bt = jnp.asarray(
            np.arange(W * self.NB, dtype=np.int32).reshape(W, self.NB)
        )
        rng = np.random.default_rng(3)
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(W, L0)),
                             jnp.int32)
        logits, pools = model.prefill_chunk_paged(
            params, prompt, self._pools(cfg, kv_dtype),
            jnp.zeros((W,), jnp.int32), jnp.full((W,), L0, jnp.int32), bt,
        )
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

        # Sequential oracle: K greedy decode_step_paged calls.
        seq_pools = jax.tree_util.tree_map(jnp.array, pools)
        lane = [tok]
        seq_logits = []
        for j in range(K):
            lg, seq_pools = model.decode_paged(
                params, lane[-1][:, None], seq_pools,
                jnp.full((W,), L0 + j, jnp.int32), bt,
            )
            seq_logits.append(lg[:, 0])
            lane.append(jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32))

        # One verify chunk over [tok, d_1 .. d_{K-1}] (self-draft lane).
        chunk = jnp.stack(lane[:K], axis=1)
        ver_logits, ver_pools = model.verify_step_paged(
            params, chunk, pools,
            jnp.full((W,), L0, jnp.int32), jnp.full((W,), K, jnp.int32), bt,
        )
        for j in range(K):
            np.testing.assert_array_equal(
                np.asarray(ver_logits[:, j]), np.asarray(seq_logits[j]),
                err_msg=f"verify position {j} != sequential decode ({impl})",
            )
        # The scattered page rows are byte-identical too (scratch page
        # excluded: both paths park masked/padding writes there).
        P = W * self.NB
        for name in pools:
            np.testing.assert_array_equal(
                np.asarray(ver_pools[name][:, :P]),
                np.asarray(seq_pools[name][:, :P]),
                err_msg=f"pool {name!r} rows diverged ({impl})",
            )

    def test_verify_adds_no_gathers(self):
        """Acceptance criterion: the verify entry point introduces zero
        XLA gathers beyond the chunk-prefill path it delegates to."""
        from repro.analysis import count_primitive
        from repro.analysis.entry_points import build_entry_points

        entries = {
            e.kind: e
            for e in build_entry_points(["stablelm-1.6b"],
                                        include_kernels=False)
            if e.variant == "pallas"
        }
        verify = entries["verify_step_paged"].jaxpr
        chunk = entries["prefill_chunk_paged"].jaxpr
        assert count_primitive(verify, "gather") == count_primitive(
            chunk, "gather"
        )


class TestRollbackFuzz:
    """Seeded random-ops fuzzer for ``rollback(n)``: after every op the
    pool conserves pages, held pages exactly cover the rolled-back
    length, and the block-table row mirrors the held pages. (The
    hypothesis-driven twin lives in test_property_spec.py.)"""

    def _fuzz(self, make_mgr, paged, seed):
        from repro.serving.cache import PageError

        rng = np.random.default_rng(seed)
        mgr = make_mgr()
        live = {}  # rid -> slot
        next_rid = 0
        for _ in range(300):
            u = rng.uniform()
            if u < 0.3 and mgr.free_slots() > 0:
                length = int(rng.integers(0, 40))
                if mgr.can_reserve(length):
                    slot = mgr.reserve(next_rid, length)
                    # The engine stamps the host mirror at dispatch time;
                    # the fuzzer plays that role here.
                    mgr.lengths[slot] = length
                    live[next_rid] = slot
                    next_rid += 1
            elif u < 0.5 and live:
                rid = int(rng.choice(list(live)))
                slot = live[rid]
                target = int(rng.integers(0, 49))
                if mgr.try_extend(rid, slot, target):
                    mgr.lengths[slot] = max(int(mgr.lengths[slot]), target)
            elif u < 0.85 and live:
                rid = int(rng.choice(list(live)))
                slot = live[rid]
                n = int(rng.integers(0, int(mgr.lengths[slot]) + 1))
                mgr.rollback(rid, slot, n)
                if paged and n > 0:
                    # Rollback trims the claim to exactly the shorter
                    # context's page need.
                    length = int(mgr.lengths[slot])
                    need = mgr.pool.blocks_for(length)
                    assert len(mgr.pages.get(rid, [])) == need
            elif live:
                rid = int(rng.choice(list(live)))
                mgr.release(rid, live.pop(rid))
            mgr.check_conservation()
            for rid, slot in live.items():
                length = int(mgr.lengths[slot])
                assert mgr.slots[slot] == rid
                if paged:
                    held = mgr.pages.get(rid, [])
                    # Pages always cover the committed mirror ...
                    if length > 0:
                        assert len(held) >= mgr.pool.blocks_for(length)
                    # ... and the block-table row mirrors them, with the
                    # tail re-scratched (no aliasing of freed pages).
                    row = list(mgr.block_table[slot])
                    assert row[: len(held)] == held
                    assert all(p == mgr.pool.scratch
                               for p in row[len(held):])
            # Over-rollback must refuse, not corrupt.
            if live:
                rid = next(iter(live))
                with pytest.raises(PageError):
                    mgr.rollback(rid, live[rid], int(mgr.lengths[live[rid]]) + 1)
                mgr.check_conservation()
        for rid, slot in list(live.items()):
            mgr.release(rid, slot)
        mgr.check_conservation()
        if paged:
            assert mgr.pool.free_pages == mgr.pool.n_pages

    @pytest.mark.parametrize("seed", [0, 1])
    def test_paged_rollback_random_ops(self, seed):
        from repro.serving.cache import PagedKVCache

        self._fuzz(
            lambda: PagedKVCache(n_slots=3, max_len=64, page_size=4,
                                 n_pages=20),
            paged=True, seed=seed,
        )

    def test_dense_rollback_random_ops(self):
        from repro.serving.cache import DenseSlotCache

        self._fuzz(lambda: DenseSlotCache(n_slots=3, max_len=64),
                   paged=False, seed=0)


@pytest.mark.slow
class TestSpecSanitizer:
    """The spec step loop's sync contract: drafts and verify argmaxes
    read back only at the commit boundary, never during dispatch, and
    per-step sanctioned syncs stay within the ``spec`` budget."""

    def _drain(self, server, cfg, n_requests=4, n_tokens=6):
        reqs = [
            server.submit(_prompt(cfg, i), n_tokens=n_tokens)
            for i in range(n_requests)
        ]
        while not all(r.done for r in reqs):
            server.step()

    def test_syncs_only_at_commit(self):
        from repro.analysis import TransferSanitizer, load_budgets

        budget = load_budgets()["host_sync"]["per_step_budget"]["spec"]
        cfg, server = _server(
            2, spec_k=4, n_groups=1, n_replicas=1,
            harvest_bounds=(60.0, 80.0), prefill_chunk=4,
        )
        self._drain(server, cfg)  # warmup: compile every dispatch shape
        with TransferSanitizer() as san:
            self._drain(server, cfg)
        assert server.stats.spec_rounds > 0
        assert san.unsanctioned_total == 0
        assert san.max_per_step <= budget
        assert san.sanctioned_by_phase["dispatch"] == 0
        assert san.sanctioned_by_phase["commit"] == san.sanctioned_total > 0
